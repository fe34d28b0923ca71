"""Jit'd public wrappers around the Pallas kernels.

The kernels lower to Mosaic on a TPU and run in Pallas interpret mode on the
CPU, where the tests validate them bit-exactly. Any other backend raises.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.hccs import hccs_rows as _hccs_rows
from repro.kernels.softmax_bf16 import softmax_bf16 as _softmax_bf16
from repro.kernels.attention import hccs_mha_fused as _hccs_mha_fused
from repro.kernels.decode import hccs_decode as _hccs_decode
from repro.kernels.decode import hccs_paged_decode as _hccs_paged_decode
from repro.kernels.decode import hccs_packed_prefill as _hccs_packed_prefill


def _interp() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels run on a TPU, or on the CPU in interpret "
            f"mode; the default backend is {backend!r}")
    return backend == "cpu"


def hccs_softmax(x_int8: jax.Array, theta: jax.Array, mode: str = "i16_div",
                 block_rows: int = 256) -> jax.Array:
    """Standalone HCCS row softmax: (N, C) int8 logits -> (N, C) int32 probs."""
    return _hccs_rows(x_int8, theta, mode=mode, block_rows=block_rows,
                      interpret=_interp())


def softmax_reference(x: jax.Array, block_rows: int = 256) -> jax.Array:
    """Exp-based BF16 softmax baseline (paper's AMD reference analogue)."""
    return _softmax_bf16(x, block_rows=block_rows, interpret=_interp())


def hccs_attention(q, k, v, scale, theta, causal: bool = True,
                   block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Fused two-pass HCCS flash-attention (see kernels/attention.py)."""
    return _hccs_mha_fused(q, k, v, scale, theta, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=_interp())


def hccs_decode(q, k, v, lengths, scale, theta, mode: str = "wide",
                static_max: bool = False, block_k: int = 128) -> jax.Array:
    """Fused single-query HCCS decode attention (see kernels/decode.py)."""
    return _hccs_decode(q, k, v, lengths, scale, theta, mode=mode,
                        static_max=static_max, block_k=block_k,
                        interpret=_interp())


def hccs_paged_decode(q, k_pool, v_pool, block_table, lengths, scale, theta,
                      mode: str = "wide", static_max: bool = False,
                      block_k: int = 128, k_scales=None,
                      v_scales=None) -> jax.Array:
    """Block-table-gather single-query HCCS decode (see kernels/decode.py).
    k_scales/v_scales (N, Hkv) f32 dequantize int8 (kv_quant) pools in-tile."""
    return _hccs_paged_decode(q, k_pool, v_pool, block_table, lengths, scale,
                              theta, mode=mode, static_max=static_max,
                              block_k=block_k, k_scales=k_scales,
                              v_scales=v_scales, interpret=_interp())


def hccs_packed_prefill(q, k_pool, v_pool, block_table, slot_ids, lengths,
                        scale, theta, mode: str = "wide",
                        static_max: bool = False, block_k: int = 128,
                        k_scales=None, v_scales=None) -> jax.Array:
    """Token-centric packed-step HCCS attention (see kernels/decode.py).
    k_scales/v_scales (N, Hkv) f32 dequantize int8 (kv_quant) pools in-tile."""
    return _hccs_packed_prefill(q, k_pool, v_pool, block_table, slot_ids,
                                lengths, scale, theta, mode=mode,
                                static_max=static_max, block_k=block_k,
                                k_scales=k_scales, v_scales=v_scales,
                                interpret=_interp())
