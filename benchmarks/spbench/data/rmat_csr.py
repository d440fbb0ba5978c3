"""Seeded R-MAT (Kronecker) graphs as CSR operands, vectorised numpy.

Edges follow the Graph500 specification's Kronecker generator: per level
of the recursion every edge draws two uniforms, ``ii = u1 > A + B`` and
``jj = u2 > (C / (C + D) if ii else A / (A + B))``, and vertex labels are
then randomly permuted. The edge-list shuffle of the reference code is
left out: CSR assembly sorts the edges anyway.

A configuration chooses what becomes of the edge list:

- ``nodes`` below ``2**scale`` folds the permuted labels onto
  ``label % nodes``, so an edge count and a node count can both be met;
- ``symmetric`` stores every edge in both directions; self-loops that
  the generator drew are dropped and repeated edges are stored once;
- ``self_loops: "added"`` then stores one ``(v, v)`` entry per node;
- ``values: "uniform_weight"`` gives each undirected edge one weight in
  (0, 1], a hash of its end points and the seed (both directions agree),
  as the Graph500 SSSP kernel draws uniform weights;
- ``values: "gcn_norm"`` gives ``1 / sqrt(deg(i) * deg(j))``, the GCN
  normalisation of ``A + I`` with degrees counted over stored entries.
"""
from __future__ import annotations

import numpy as np

from repro.core import formats as F
from repro.core.tensor import LevelData, Tensor

#: Stream numbers of the seed, so that the graph and the dense operands
#: of one seed draw from independent generators.
GRAPH_STREAM = 1


def kronecker_edges(scale: int, n_edges: int, initiator, rng) -> tuple:
    """(src, dst) int32 labels in [0, 2**scale) of ``n_edges`` R-MAT edges,
    before the labels are permuted. ``scale`` is at most 30."""
    a, b, c, d = (float(x) for x in initiator)
    if abs(a + b + c + d - 1.0) > 1e-9:
        raise ValueError(f"initiator {initiator} does not sum to 1")
    ab, c_norm, a_norm = a + b, c / (c + d), a / (a + b)
    if not 0 < scale <= 30:
        raise ValueError(f"scale {scale} outside 1..30")
    src = np.zeros(n_edges, np.int32)
    dst = np.zeros(n_edges, np.int32)
    for level in range(scale):
        ii = rng.random(n_edges, dtype=np.float32) > ab
        thresh = np.where(ii, np.float32(c_norm), np.float32(a_norm))
        jj = rng.random(n_edges, dtype=np.float32) > thresh
        src |= ii.astype(np.int32) << level
        dst |= jj.astype(np.int32) << level
    return src, dst


def _hash_unit(keys: np.ndarray, seed: int) -> np.ndarray:
    """splitmix64 of ``keys`` and the seed, mapped to float32 in (0, 1]."""
    z = keys.astype(np.uint64) + np.uint64((seed * 0x9E3779B97F4A7C15)
                                           & 0xFFFFFFFFFFFFFFFF)
    with np.errstate(over="ignore"):
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return (((z >> np.uint64(40)) + np.uint64(1)).astype(np.float32)
            * np.float32(2.0 ** -24))


def csr_from_keys(name: str, n: int, keys: np.ndarray,
                  vals: np.ndarray) -> Tensor:
    """CSR Tensor from sorted, unique ``row * n + col`` keys."""
    rows = (keys // n).astype(np.int32)
    crd = (keys % n).astype(np.int32)
    pos = np.zeros(n + 1, np.int32)
    np.cumsum(np.bincount(rows, minlength=n), out=pos[1:])
    fmt = F.CSR()
    levels = [LevelData(fmt.levels[0], n),
              LevelData(fmt.levels[1], n, pos=pos, crd=crd)]
    return Tensor(name, (n, n), fmt, levels, vals.astype(np.float32),
                  np.float32)


def generate(cfg: dict, seed: int, name: str = "B") -> Tensor:
    """The configuration's sparse operand for ``seed``."""
    if cfg.get("format", "CSR") != "CSR":
        raise ValueError(f"this generator builds CSR, not {cfg['format']!r}")
    scale = int(cfg["scale"])
    n = int(cfg.get("nodes", 1 << scale))
    if n > 1 << scale:
        raise ValueError(f"nodes {n} exceed 2**scale = {1 << scale}")
    n_edges = int(cfg.get("edges", int(cfg.get("edgefactor", 0)) << scale))
    if not cfg.get("symmetric", False):
        raise ValueError("only symmetric graphs are generated")
    rng = np.random.default_rng([GRAPH_STREAM, seed])
    src, dst = kronecker_edges(scale, n_edges, cfg["initiator"], rng)
    perm = rng.permutation(1 << scale)
    src, dst = perm[src], perm[dst]
    if n < 1 << scale:
        src %= n
        dst %= n
    off = src != dst
    src, dst = src[off], dst[off]
    parts = [src * n + dst, dst * n + src]
    loops = cfg.get("self_loops", "dropped")
    if loops == "added":
        v = np.arange(n, dtype=np.int64)
        parts.append(v * n + v)
    elif loops != "dropped":
        raise ValueError(f"self_loops {loops!r}")
    del src, dst, off
    keys = np.sort(np.concatenate(parts))
    del parts
    keep = np.empty(keys.shape[0], bool)
    keep[0] = True
    np.not_equal(keys[1:], keys[:-1], out=keep[1:])
    keys = keys[keep]
    del keep
    kind = cfg["values"]
    if kind == "uniform_weight":
        r, c = keys // n, keys % n
        vals = _hash_unit(np.minimum(r, c) * n + np.maximum(r, c), seed)
    elif kind == "gcn_norm":
        r, c = keys // n, keys % n
        deg = np.bincount(r, minlength=n).astype(np.float64)
        vals = (1.0 / np.sqrt(deg[r] * deg[c])).astype(np.float32)
    else:
        raise ValueError(f"values {kind!r}")
    return csr_from_keys(name, n, keys, vals)
