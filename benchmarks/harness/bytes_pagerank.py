"""Bytes a PageRank sweep needs to move, from shapes alone (kept with
the benchmark, like ``harness/bytes_als.py``, so that no PR which claims
a gain can change what ``pagerank_spmv_roofline`` is a share of)."""

from __future__ import annotations


def sweep_bytes_needed(shapes: dict) -> int:
    """Per chip and sweep, whatever layout implements it: a distinct
    edge is its two vertex ids (8 B: the source to read a rank by, the
    destination to add to); a vertex is its rank read, its reciprocal
    out-degree read and its new rank written (12 B). 2.28e9 at SCALE 24
    (260M distinct edges, 16.8M vertices). Not counted: the padding
    slots of a plan, a per-edge weight held where the per-vertex one
    would do, window-relative indices held as four words (the fused
    plan holds 20 B a slot), the ranks read once an edge and not once a
    vertex."""
    edges = -(-shapes["n_edges"] // shapes["n_shards"])
    return (edges * shapes["edge_bytes_needed"]
            + shapes["n_vertices"] * shapes["vertex_bytes_needed"])
