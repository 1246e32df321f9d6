"""Pallas kernel correctness sweeps (interpret=True) vs pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.maxplus.kernel import maxplus_fold_kernel
from repro.kernels.maxplus.ref import maxplus_fold_ref

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("b,p,n,t", [(4, 8, 18, 40), (130, 4, 18, 17), (1, 2, 6, 9)])
def test_maxplus_kernel_matches_ref(b, p, n, t):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, b * p + t))
    mats = jax.random.uniform(k1, (b, p, n, n), jnp.float32, 0.0, 10.0)
    mats = jnp.where(jax.random.bernoulli(k2, 0.4, mats.shape), mats, -1e30)
    s0 = jnp.zeros((b, n), jnp.float32)
    out = maxplus_fold_kernel(mats, s0, t_steps=t)
    ref = maxplus_fold_ref(mats, s0, t_steps=t)
    assert np.allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-3)
