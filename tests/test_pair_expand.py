"""The pair kernels' shared device tail (ir/evaljax.py `_pair_expand`):
bit-packed verdicts [R, W] -> a header with the true pair count, then
the first pcap (row, constraint) pairs in row-major order, against a
plain unpack-and-nonzero in numpy."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from gatekeeper_tpu.ir.evaljax import _pair_expand


def _want(packed, valid, row0, c, pcap):
    bits = ((packed[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1)
    flat = bits.reshape(packed.shape[0], -1).astype(bool)
    flat &= valid[:, None]
    flat[:, c:] = False
    rows, cols = np.nonzero(flat)
    body = np.zeros((pcap, 2), np.uint32)
    n = min(pcap, rows.size)
    body[:n, 0] = rows[:n] + row0
    body[:n, 1] = cols[:n]
    return rows.size, body


@pytest.mark.parametrize("r,w,c,density,pcap", [
    (4096, 1, 1, 0.01, 64),      # one constraint, sparse: the audit case
    (4096, 1, 1, 0.5, 64),       # more pairs than the capacity
    (1000, 1, 17, 0.2, 4096),    # C inside one word, padding bits set
    (300, 3, 70, 0.3, 8192),     # three words, the last one partial
    (512, 2, 64, 0.0, 16),       # nothing fires
    (257, 4, 128, 1.0, 40000),   # everything fires
])
def test_pair_expand_matches_numpy(r, w, c, density, pcap):
    rng = np.random.default_rng(r * 7 + w)
    bits = rng.random((r, w * 32)) < density
    packed = (bits.reshape(r, w, 32).astype(np.uint64)
              << np.arange(32, dtype=np.uint64)).sum(-1).astype(np.uint32)
    valid = rng.random(r) < 0.9
    row0 = 12345
    out = np.asarray(jax.jit(_pair_expand, static_argnums=4)(
        packed, valid, np.int32(row0), np.int32(c), pcap))
    count, body = _want(packed, valid, row0, c, pcap)
    assert out.shape == (pcap + 1, 2)
    assert int(out[0, 0]) == count
    np.testing.assert_array_equal(out[1:], body)
