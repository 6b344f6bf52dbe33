"""The overflow list of a tile block, and the one place that knows its form.

A tile block's pairs past a cell's cap ride beside the pair words as a
list, in one of two forms, each two arrays of the block's dict:

* COO (``ovf_b``, ``ovf_r``): a bucket and a row a slot, room-long, the
  pairs first and ``UNUSED`` buckets after them. What every writer and
  encoder makes (``tilemm.cap_overflow``), and what an eval pass, a short
  list and a list of mostly distinct buckets step as it is: a gather and
  a scatter-add a slot.
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
    """The block's list alone, in the form the block brings it."""
    return {k: block[k] for k in names(is_hot(block))}


def pairs(ovf_b: np.ndarray) -> int:
    """The pairs on a COO list: its slots that are in use."""
    return int(np.count_nonzero(ovf_b != UNUSED))


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
