"""Public op: WLSH featurization with automatic padding around the kernel."""
from __future__ import annotations

import jax.numpy as jnp

from ...core.bucket_fns import BucketFn
from ...core.lsh import Features, LSHParams
from .kernel import BLOCK_N, featurize_pallas


def featurize_op(params: LSHParams, f: BucketFn, x, *,
                 interpret: bool) -> Features:
    """Drop-in replacement for repro.core.lsh.featurize backed by the Pallas
    kernel.  Points are padded to the kernel block size and trimmed after;
    ``interpret`` selects the Pallas interpreter (CPU only)."""
    n = x.shape[0]
    bn = min(BLOCK_N, max(128, -(-n // 128) * 128))
    np_ = -(-n // bn) * bn
    xp = jnp.pad(jnp.asarray(x, jnp.float32), ((0, np_ - n), (0, 0)))
    k1, k2, wt, sg = featurize_pallas(xp, params.w, params.z, params.r1,
                                      params.r2, f=f, interpret=interpret,
                                      block_n=bn)
    return Features(key1=k1[:, :n], key2=k2[:, :n], weight=wt[:, :n],
                    sign=sg[:, :n])
