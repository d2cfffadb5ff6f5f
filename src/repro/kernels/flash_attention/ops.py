"""Public flash-attention wrapper: pads the sequence to the block size."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_kernel
from repro.utils import interpret_mode


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal GQA attention. q: (B, Hq, S, D); k, v: (B, Hkv, S, D)."""
    interpret = interpret_mode(interpret)
    b, hq, s, d = q.shape
    blk = max(block_q, block_k)
    pad = (-s) % blk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        # padded KV columns must not receive weight: causal masking handles the
        # q<k side; for pure padding rows the outputs are sliced off below, and
        # padded KV keys score exp(0·k)=uniform only against padded queries.
        if not causal:
            raise ValueError("non-causal padding not supported; pad upstream")
    out = flash_attention_kernel(
        q, k, v, causal=causal, block_q=min(block_q, q.shape[2]),
        block_k=min(block_k, q.shape[2]), interpret=interpret,
    )
    return out[:, :, :s] if pad else out
