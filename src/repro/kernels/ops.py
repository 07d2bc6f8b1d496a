"""Jitted public wrappers for the Pallas kernels.

On a TPU backend the kernels lower to Mosaic; on any other backend they
run in interpret mode (the Pallas interpreter runs the kernel body as
plain XLA). `interpret` defaults accordingly, so the public API is
portable, and `kernel_backend()` names which of the two a call takes.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dse_eval import BLOCK_C, dse_eval, dse_eval_batched
from repro.kernels.swa_attention import swa_attention
from repro.kernels.ws_matmul import ws_matmul
from repro.obs.metrics import metrics as _obs_metrics
from repro.obs.trace import tracer as _obs_tracer

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_backend() -> str:
    """"mosaic" where the kernels compile for the TPU, else "interpret"."""
    return "interpret" if _default_interpret() else "mosaic"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. `JAX_COMPILATION_CACHE_DIR`, when set, is used
    as JAX reads it; otherwise the cache lives at `<repo>/.jax_cache` (a
    fixed path, since the path is part of every cache key)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def matmul(a, w, *, block_m=128, block_n=128, block_k=128, schedule="ws",
           interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return ws_matmul(a, w, block_m=block_m, block_n=block_n,
                     block_k=block_k, schedule=schedule, interpret=interpret)


def attention(q, k, v, *, window=None, block_q=128, block_kv=128,
              interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return swa_attention(q, k, v, window=window, block_q=block_q,
                         block_kv=block_kv, interpret=interpret)


def _pad(configs, block_c):
    """Pad a (C, 2) config list up to a multiple of the kernel block by
    repeating the last design point. The block is `block_c` if given, else
    BLOCK_C, or C itself (a full-extent block) when C is smaller.
    Returns (padded configs, C, block)."""
    configs = np.asarray(configs, np.float32)
    C = len(configs)
    block_c = block_c or min(BLOCK_C, C)
    pad = (-C) % block_c
    if pad:
        configs = np.concatenate([configs, np.repeat(configs[-1:], pad, 0)])
    return jnp.asarray(configs), C, block_c


def sweep(configs, layers, *, block_c=None, interpret=None, **model_kw):
    """DSE sweep kernel over a (C, 2) config list and an (L, 5) layer
    table -> (C, 8) OUT_COLS rows. Configs are padded to the kernel block
    (repeating the last design point) and the result sliced back to C;
    `model_kw` passes dataflow/precision/accounting options through to
    the shared model core (see kernels/dse_eval.py).

    Counts one `kernels.sweep_dispatches` per call — here in the plain
    wrapper, NOT inside the jitted `dse_eval` (which only runs its Python
    body at trace time), so the counter reflects actual dispatches.
    Traces `sweep.put` (padding and the copy to the device) and
    `sweep.enqueue` (the kernel call and the slice, both asynchronous)."""
    _obs_metrics().inc("kernels.sweep_dispatches")
    interpret = _default_interpret() if interpret is None else interpret
    tr = _obs_tracer()
    with tr.span("sweep.put", "dse"):
        padded, C, block_c = _pad(configs, block_c)
    with tr.span("sweep.enqueue", "dse"):
        return dse_eval(padded, layers, block_c=block_c,
                        interpret=interpret, **model_kw)[:C]


def sweep_batched(configs, layer_sets, *, block_c=None, interpret=None,
                  **model_kw):
    """Fused (scenario, config) sweep kernel over batched layer sets —
    S scenarios x C configs in one dispatch -> (S, C, 8); configs are
    padded and sliced as in `sweep` (see kernels/dse_eval.py).

    Counts one `kernels.fused_dispatches` per call (in the wrapper, not
    the jitted body) — the counter the "ONE fused dispatch per sweep"
    regression tests assert on. Traces `sweep.put` and `sweep.enqueue`
    as `sweep` does."""
    _obs_metrics().inc("kernels.fused_dispatches")
    interpret = _default_interpret() if interpret is None else interpret
    tr = _obs_tracer()
    with tr.span("sweep.put", "dse"):
        padded, C, block_c = _pad(configs, block_c)
    with tr.span("sweep.enqueue", "dse"):
        return dse_eval_batched(padded, layer_sets, block_c=block_c,
                                interpret=interpret, **model_kw)[:, :C]
