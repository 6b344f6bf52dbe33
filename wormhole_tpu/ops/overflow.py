"""The overflow list of a tile block, and the one place that knows its form.

A tile block's pairs past a cell's cap ride beside the pair words as a
list, in one of two forms, each two arrays of the block's dict:

* COO (``ovf_b``, ``ovf_r``): a bucket and a row a slot, room-long, the
  pairs first and ``UNUSED`` buckets after them. What every writer and
  encoder makes (``tilemm.cap_overflow``), and what an eval pass, a short
  list and a list of mostly distinct buckets step as it is: a gather and
  a scatter-add a slot. A long one may cross with two arrays more
  (``ovf_d``, ``ovf_k``: ``distinct``), its distinct buckets and each
  slot's place among them, for a step that reads a plane once a listed
  bucket and not once a slot.
* hot (``ovf_u``, ``ovf_pw``): the list's distinct buckets in whole hot
  tiles (``UNUSED`` after them) and the pairs as pair words over their
  rank among those (``tilemm.encode_hot``), where the feed's
  ``data/crec.HotRoom`` takes a train block's long list of few buckets:
  it runs through the multi-channel kernel pair.

A form is a pytree structure, so a jitted step has a program for each and
asks here which one it is tracing (``of``, ``pick``). Host code only:
numpy, so that the feeds can ask too.
"""

from __future__ import annotations

import numpy as np

UNUSED = np.uint32(0xFFFFFFFF)   # an unused slot of ovf_b / ovf_u
COO = ("ovf_b", "ovf_r")
HOT = ("ovf_u", "ovf_pw")
DISTINCT = ("ovf_d", "ovf_k")
# a COO list crosses with its distinct buckets where it has at least this
# many slots to each slot of their room: under that the room's own reads
# outweigh what the slots' save
DISTINCT_MIN_SLOTS = 4


def names(hot: bool) -> tuple:
    """The two array names of a form."""
    return HOT if hot else COO


def is_hot(block: dict) -> bool:
    """Does this block (or group of blocks, or list) bring its list in
    the hot form?"""
    return HOT[1] in block


def array(block: dict):
    """The array a block's list is known by, in whichever form it
    crossed (the hot form's pair words, else the COO buckets); None
    where the block brings no list."""
    return block.get(HOT[1], block.get(COO[0]))


def has_list(block: dict) -> bool:
    return array(block) is not None


def of(block: dict) -> dict:
    """The block's list alone, in the form the block brings it (a COO
    list with its distinct buckets where it crossed with them)."""
    hot = is_hot(block)
    extra = () if hot or DISTINCT[0] not in block else DISTINCT
    return {k: block[k] for k in names(hot) + extra}


def distinct_of(lst: dict):
    """``(ovf_d, ovf_k)`` where the list crossed with them, else None."""
    return (tuple(lst[k] for k in DISTINCT) if DISTINCT[0] in lst
            else None)


def pairs(ovf_b: np.ndarray) -> int:
    """The pairs on a COO list: its slots that are in use."""
    return int(np.count_nonzero(ovf_b != UNUSED))


def distinct(ovf_b: np.ndarray, tiles: int, tile: int):
    """``(ovf_d, ovf_k)`` of a COO list, or None where the list is too
    short for them (``DISTINCT_MIN_SLOTS``). ``ovf_d``: the list's
    distinct buckets in ascending order, padded with ``UNUSED`` to whole
    tiles of ``tile`` buckets, ``tiles`` of them at least (a room is a
    shape, and a shape a program of the step: the caller keeps the widest
    it has met). ``ovf_k``: each slot's index in ``ovf_d``. A gather a
    slot from a table plane follows WHICH addresses the list names: a
    click-log list names 40,000 buckets in 1.1M pairs, a few of them tens
    of thousands of times, and a step that asks the plane for every slot
    runs 2% faster or slower by the seed that made the keys (chip, PR 51).
    Read once a bucket, the plane is asked for 40,000 values and the
    slots read those. An unused slot's index is dealt round the room, so
    that the unused slots ask for no one address either; its value is
    masked out by ``ovf_b`` as before."""
    used = ovf_b != UNUSED
    uniq, inv = np.unique(ovf_b[used], return_inverse=True)
    room = max(tiles, -(-len(uniq) // tile)) * tile
    if room * DISTINCT_MIN_SLOTS > len(ovf_b):
        return None
    ovf_d = np.full(room, UNUSED, np.uint32)
    ovf_d[:len(uniq)] = uniq
    ovf_k = np.empty(len(ovf_b), np.uint32)
    ovf_k[used] = inv
    ovf_k[~used] = np.arange(len(ovf_b) - len(inv)) % room
    return ovf_d, ovf_k


def crossing(block: dict, drop_empty: bool) -> dict:
    """The block as it crosses to the device: a list that comes with its
    hot form crosses as that alone (the step reads nothing of the pairs
    themselves), and with ``drop_empty`` a COO list with no pair in it
    stays behind, so that its block takes the step that has no list to
    scatter. Writers fill a list from the front: one look settles a list
    that has pairs, a scan only one that seems empty."""
    ovf_b = block.get(COO[0])
    if is_hot(block) or (drop_empty and ovf_b is not None
                         and not (ovf_b[:1] != UNUSED).any()
                         and not pairs(ovf_b)):
        return {k: v for k, v in block.items() if k not in COO}
    return block


def pick(lst: dict, coo_helper, hot_helper) -> tuple:
    """``(helper, first, second)`` for a list phase of a step: the helper
    of the form ``lst`` is in and the form's two arrays, which every
    list helper of ops/tilemm.py takes side by side."""
    hot = is_hot(lst)
    first, second = names(hot)
    return (hot_helper if hot else coo_helper), lst[first], lst[second]
