"""What wide&deep needs for one block, whatever the formulation.

``block_work`` counts the embedding side, as ``criteo_fm``'s does, so that
``tile_kernel_roofline.replay`` stays the kernel pair's share. bytes: the
block's pair words (one u32 a pair) and labels (one byte a row) read once, and
for each distinct bucket the block touches its state read once and written
once (models/wide_deep.py keeps 2 x (1 + dim) f32 a bucket: w, v and their
AdaGrad accumulators). operations: 2 FLOPs a pair and channel forward and as
many backward (channels: w and the dim embedding values pulled; the dual, the
dim gradients and the count pushed: dim + 2 at the widest).

``tower_flops`` counts the dense tower for ``tower_mxu_roofline.replay``: three
matmuls a layer and row (``h W`` forward; ``g W^T`` and ``h^T g`` backward,
the first layer's input gradient included: the embeddings train), 2 FLOPs a
multiply-add, over the layers dim -> hidden... -> 1. Element-wise work (bias,
ReLU, the dual) is not counted.
"""


def block_work(config: dict, pairs: int, rows: int,
               distinct_buckets: int) -> dict:
    state = int(config["state_bytes_per_bucket"])
    channels = int(config["dim"]) + 2
    return {"bytes": 4 * pairs + rows + 2 * state * distinct_buckets,
            "flops": 2 * 2 * pairs * channels}


def tower_flops(config: dict, rows: int) -> int:
    sizes = [int(config["dim"]), *(int(h) for h in config["hidden"]), 1]
    return 6 * rows * sum(a * b for a, b in zip(sizes, sizes[1:]))
