"""`rows_from_pos` gives the row ids the binary search defines.

The oracle keeps the old definition, ``clip(searchsorted(pos, p, "right")
- 1, 0, R-1)``, in numpy. The counting form (one scatter-add and a prefix
sum) must give the same integers for every monotone ``pos``, hypersparse
windows (R much larger than N) included, and never run the binary search,
a loop of gathers on the device.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as K


def _oracle(pos, n_positions):
    r = np.searchsorted(pos, np.arange(n_positions), side="right") - 1
    return np.clip(r, 0, len(pos) - 2)


def _loop_ops(hlo_text):
    """Opcodes reachable from the body of any ``while`` in compiled HLO
    text, through the fusions and calls it makes."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        if name is None:
            m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
            if m:
                name = m.group(1)
                comps[name] = []
        elif line == "}":
            name = None
        else:
            comps[name].append(line)
    todo = [b for lines in comps.values() for line in lines
            if " while(" in line
            for b in re.findall(r"body=%([\w.\-]+)", line)]
    ops, seen = [], set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            m = re.search(r"= \S+ ([\w\-]+)\(", line)
            if m:
                ops.append(m.group(1))
            todo += [r for r in re.findall(r"%([\w.\-]+)", line)
                     if r in comps]
    return ops


def _random_pos(seed, n_rows, n_positions):
    """Monotone pos with empty rows, a positive start and padded tail."""
    rng = np.random.default_rng(seed)
    starts = np.sort(rng.integers(0, n_positions, n_rows + 1))
    starts[rng.random(n_rows + 1) < 0.2] = starts[0]     # empty leading rows
    return np.sort(starts)


# name -> (pos, n_positions)
CASES = {
    "empty_leading_rows": ([0, 0, 0, 3, 5, 8], 8),
    "empty_trailing_rows": ([0, 2, 5, 8, 8, 8], 8),
    "every_row_empty": ([0, 0, 0, 0], 4),
    "one_row": ([0, 5], 5),
    "one_row_padded": ([0, 3], 7),
    "pos0_positive": ([3, 5, 9, 12], 12),
    "padded_past_last": ([0, 2, 4, 6], 10),
    "starts_past_last_position": ([0, 2, 9, 11, 11], 6),
    "random_0": (_random_pos(0, 40, 300), 300),
    "random_1": (_random_pos(1, 300, 300), 320),
    "random_2": (_random_pos(2, 1000, 120), 120),
    "hypersparse": (np.sort(np.random.default_rng(3).integers(0, 6, 1001)),
                    6),
    "hypersparse_empty_rows": (np.r_[np.zeros(600, int), np.full(401, 2)],
                               2),
}


@pytest.mark.parametrize("name", list(CASES))
def test_rows_from_pos_matches_binary_search(name):
    pos, n_positions = CASES[name]
    pos = np.asarray(pos, np.int32)
    compiled = jax.jit(K.rows_from_pos, static_argnums=1).lower(
        jnp.asarray(pos), n_positions).compile()
    got = np.asarray(compiled(jnp.asarray(pos)))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, _oracle(pos, n_positions))
    # No binary search: nothing gathers inside a loop.
    assert "gather" not in _loop_ops(compiled.as_text())


def test_rows_from_pos_vmapped_over_pieces():
    """Leaves are vmapped over stacked shard pieces of one padded shape."""
    n_positions = 64
    pos = np.stack([_random_pos(s, 20, n_positions) for s in range(4)])
    got = np.asarray(jax.vmap(K.rows_from_pos, in_axes=(0, None))(
        jnp.asarray(pos, jnp.int32), n_positions))
    for piece, p in zip(got, pos):
        np.testing.assert_array_equal(piece, _oracle(p, n_positions))


@pytest.mark.parametrize("leaf, dense", [
    (K.leaf_spmm_rows, np.ones((16, 8), np.float32)),
    (K.leaf_spmv_rows, np.ones(16, np.float32)),
])
def test_row_leaf_runs_no_search_loop(leaf, dense):
    """With at least as many positions as rows, the row leaf counts row
    starts: no loop of its compiled program gathers (the binary search's
    ``pos[mid]``); the prefix sum's loop only shifts and adds."""
    n_rows, n_positions = 50, 200
    pos = _random_pos(4, n_rows, n_positions).astype(np.int32)
    crd = np.random.default_rng(5).integers(0, 16, n_positions).astype(
        np.int32)
    vals = np.ones(n_positions, np.float32)
    text = jax.jit(leaf).lower(pos, crd, vals, dense).compile().as_text()
    assert "gather" not in _loop_ops(text)
    assert "gather" in text          # the leaf's own gather of the operand
