"""Work of one split-KV paged decode read: ``B`` queries of ``H`` heads of
width ``Dh`` against ``KVH`` key/value heads over each slot's ``lens[b]``
cached positions.

FLOPs: scores and the weighted sum, ``4·H·Dh`` per (query, position) pair,
``4·H·Dh·Σ_b lens[b]`` (a multiply-add counts 2; softmax terms left out).
Bytes: each slot's keys and values once, ``2·KVH·Dh·kv_bytes·Σ_b lens[b]``,
plus the queries and outputs (``2·B·H·Dh·q_bytes``).
"""


def fwd(lens, H, KVH, Dh, kv_bytes=2, q_bytes=2):
    n = int(sum(lens))
    return 4 * H * Dh * n, 2 * KVH * Dh * kv_bytes * n + 2 * len(lens) * H * Dh * q_bytes
