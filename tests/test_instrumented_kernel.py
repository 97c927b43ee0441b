"""In-kernel device-side event recording (fine-grained Table-II tier)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.instrumented_matmul import (matmul_traced_pallas,
                                               matmul_traced_ref, BM, BN)
import repro.core as pasta


@pytest.mark.parametrize("m,k,n", [(128, 64, 128), (256, 128, 384),
                                   (384, 32, 128)])
def test_traced_matmul_matches_oracle(rng, m, k, n):
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    out, trace = matmul_traced_pallas(x, w, interpret=True)
    out_ref, trace_ref = matmul_traced_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(trace), np.asarray(trace_ref))


def test_trace_buffer_flows_through_pasta(handler, rng):
    """The device trace surfaces as a TRACE_BUFFER event whose aggregate the
    tools consume — never the raw records."""
    x = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    out, trace = matmul_traced_pallas(x, w, interpret=True)
    seen = []
    proc = pasta.EventProcessor(handler)
    handler.subscribe(lambda e: seen.append(e), kinds=("trace_buffer",))
    handler.trace_buffer(np.asarray(trace), name="matmul",
                         kernel="matmul_traced",
                         bytes_read=int(np.asarray(trace)[:, 2].sum()),
                         bytes_written=int(np.asarray(trace)[:, 3].sum()))
    assert len(seen) == 1
    ev = seen[0]
    assert ev.attrs["bytes_read"] == (256 // BM) * (256 // BN) * \
        (BM * 64 * 4 + 64 * BN * 4)
