"""Bytes that the counting algorithms need, computed from shapes: the
numerators of the roofline shares. Each counts what the algorithm has to
read, not what an implementation happens to move, so a faster
implementation raises the share and a wasteful one lowers it.
"""
from __future__ import annotations


def words(n_nodes: int) -> int:
    """32-bit words in one adjacency bitset row of ``n_nodes`` vertices."""
    return -(-int(n_nodes) // 32)


def bitset_count_bytes(n_nodes: int, n_edges: int) -> int:
    """One resident bitset count of a simple graph (the paper's
    intersection of neighbour sets, as AND + popcount over bitset rows):
    each of the m edges reads the two endpoint rows, ceil(n/32) words of
    4 bytes each, and its own two int32 endpoints. The graph's own width,
    not a padded one: 2 * m * ceil(n/32) * 4 + 8 * m."""
    m = int(n_edges)
    return 2 * m * words(n_nodes) * 4 + 8 * m


def ingest_bytes(block_size: int, n_nodes: int, n_stages: int = 1,
                 epochs: int = 1) -> int:
    """One block of the two-phase stream ingest, per stage shard: each of
    the B edges gathers four rows (the state's and the block delta's, at
    both endpoints) of the shard's W_s = ceil(ceil(n/32) / S) words of 4
    bytes, against each of the E live epoch tables (E = 1 unbounded):
    16 * B * W_s * E."""
    w_s = -(-words(n_nodes) // int(n_stages))
    return 16 * int(block_size) * w_s * int(epochs)
