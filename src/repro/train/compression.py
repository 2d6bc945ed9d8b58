"""Error-feedback int8 gradient compression for the cross-pod reduction.

The paper's float->int rewrite (Section 4.4) pays off exactly where
precision is cheap and bandwidth is dear.  In a multi-pod mesh the one
mandatory slow-link collective is the per-step gradient reduction over
``pod``; compressing it to int8 cuts the DCN bytes ~4x.  Error feedback
(Seide et al.; 1-bit SGD lineage) keeps the quantization *residual* locally
and re-injects it next step, so compression error accumulates to O(1)
instead of O(T) and convergence is preserved (unit-tested on a quadratic
and a tiny LM in ``tests/test_train.py``).

Mechanics per tensor:
    y      = grad + err                     (re-inject residual)
    q, s   = int8 quantize(y)               (per-tensor symmetric scale)
    total  = sum over pods of dequant(q, s) (all_gather int8+scale, local sum)
    err'   = y - dequant(q, s)              (what this pod failed to send)

The all_gather moves ``P x (n/4 + 4)`` bytes instead of the ~``2n`` of a
ring all-reduce in f32 — visible in the dry-run HLO as int8 collective
operands (``launch/dryrun.py`` artifacts; ROADMAP.md tracks the
collective-bound follow-ups).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class CompressionState(NamedTuple):
    err: Any    # pytree of f32 residuals, shaped like grads


def init_compression(grads_like: Any) -> CompressionState:
    return CompressionState(
        jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)
    )


def _quantize(y: jax.Array):
    amax = jnp.max(jnp.abs(y))
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(y / scale), -128, 127).astype(jnp.int8)
    return q, scale


def compress_decompress(x: jax.Array, err: jax.Array):
    """Single-tensor round trip (what one pod contributes + new residual)."""
    y = x.astype(jnp.float32) + err
    q, scale = _quantize(y)
    deq = q.astype(jnp.float32) * scale
    return deq, y - deq


def compressed_allreduce(x: jax.Array, err: jax.Array, axis_name: str):
    """Mean over ``axis_name`` of int8-compressed contributions.

    Must run inside ``shard_map`` manual over ``axis_name``.  Returns
    (mean, new_err).
    """
    y = x.astype(jnp.float32) + err
    q, scale = _quantize(y)
    deq_own = q.astype(jnp.float32) * scale
    # int8 payload + f32 scale over the slow link
    qs = jax.lax.all_gather(q, axis_name)      # (P, ...)
    ss = jax.lax.all_gather(scale, axis_name)  # (P,)
    n = qs.shape[0]
    total = jnp.tensordot(
        ss, qs.astype(jnp.float32).reshape(n, -1), axes=1
    ).reshape(x.shape)
    return total / n, y - deq_own


def compressed_allreduce_tree(grads: Any, state: CompressionState,
                              axis_name: str):
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(state.err)
    outs = [compressed_allreduce(g, e, axis_name)
            for g, e in zip(flat_g, flat_e)]
    mean = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_err = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return mean, CompressionState(new_err)
