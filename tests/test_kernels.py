"""Per-kernel allclose vs pure-jnp oracles, swept over shapes/dtypes
(interpret mode executes the kernel bodies on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.model_core import Precision
from repro.kernels import ops, ref

MM_SHAPES = [(128, 128, 128), (256, 384, 128), (128, 512, 256)]


@pytest.mark.parametrize("M,K,N", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("schedule", ["ws", "os"])
def test_ws_matmul(M, K, N, dtype, schedule):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(M, K)), dtype)
    w = jnp.asarray(rng.normal(size=(K, N)), dtype)
    got = ops.matmul(a, w, schedule=schedule, interpret=True)
    want = ref.ws_matmul_ref(a, w)
    tol = 1e-4 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
def test_ws_matmul_block_shapes(blocks):
    bm, bn = blocks
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    got = ops.matmul(a, w, block_m=bm, block_n=bn, block_k=128,
                     interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.ws_matmul_ref(a, w)),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("S,D", [(256, 64), (256, 128), (512, 64)])
@pytest.mark.parametrize("window", [None, 128, 64])
def test_swa_attention(S, D, window):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, S, D)), jnp.float32)
    got = ops.attention(q, k, v, window=window, interpret=True)
    want = ref.swa_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_swa_attention_bf16():
    rng = np.random.default_rng(3)
    mk = lambda: jnp.asarray(rng.normal(size=(2, 256, 64)), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    got = ops.attention(q, k, v, window=128, interpret=True)
    want = ref.swa_attention_ref(q, k, v, window=128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("net,n_cfgs", [("resnet152", 896),
                                         ("densenet201", 961)])
def test_dse_eval_vs_float64_model(net, n_cfgs):
    """Long tables (156 and 201 rows) summed row by row, and 896 and 961
    configs, neither a multiple of the 1,024-config tile: the padded tail
    and the long sums must both come back exact to f32 roundoff."""
    from repro.core.cnn_zoo import get_workloads
    from repro.core.dse import grid_axes
    layers = np.asarray(get_workloads(net), np.float32)
    hs = grid_axes()
    H, W = np.meshgrid(hs, hs, indexing="ij")
    cfgs = np.stack([H.reshape(-1), W.reshape(-1)], 1)[:n_cfgs]
    got = np.asarray(ops.sweep(jnp.asarray(cfgs, jnp.float32),
                               jnp.asarray(layers), interpret=True))
    assert got.shape == (n_cfgs, 8)
    want = ref.dse_eval_ref(cfgs, layers)
    rel = np.abs(got - want) / (np.abs(want) + 1.0)
    assert rel.max() < 1e-5


@pytest.mark.parametrize("model_kw", [
    {},
    {"dataflow": "os"},
    {"dataflow": "multi_array", "n_arrays": 4},
    {"act_reread": True, "count_weight_load_hops": True},
    {"idle_pe_energy": 0.2},
    {"dataflow": "os", "precision": Precision(16, 4, 16)},
    {"precision": Precision(4, 8, 16), "count_weight_load_hops": True},
], ids=lambda kw: "-".join(map(str, kw.values())) or "default")
def test_batched_kernel_vs_float64_model(model_kw):
    """Ragged 3-, 9- and 130-row tables in one batched call on the 961
    grid, under each dataflow and accounting option: every table summed
    to its own length, a groups = 0 row inside the longest counted as
    absent, against the float64 closed forms."""
    from repro.core.cnn_zoo import get_workloads
    from repro.core.dse import grid_axes, pad_layer_sets
    rows = get_workloads("densenet201")
    tables = [rows[:3], rows[3:12], rows[12:142]]
    sets = pad_layer_sets(tables)
    sets[2, 60, 3] = 0.0
    hs = grid_axes()
    H, W = np.meshgrid(hs, hs, indexing="ij")
    cfgs = np.stack([H.reshape(-1), W.reshape(-1)], 1)
    got = np.asarray(ops.sweep_batched(cfgs, sets, interpret=True,
                                       **model_kw))
    assert got.shape == (3, 961, 8)
    for s, t in enumerate(tables):
        layers = np.asarray(t[:60] + t[61:] if s == 2 else t, np.float32)
        want = ref.dse_eval_ref(cfgs, layers, **model_kw)
        rel = np.abs(got[s] - want) / (np.abs(want) + 1.0)
        assert rel.max() < 1e-5, (s, model_kw, rel.max())


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """`use_compile_cache` leaves a set JAX_COMPILATION_CACHE_DIR to JAX
    and otherwise points the cache at the fixed in-checkout `.jax_cache`;
    importing the kernels sets nothing."""
    import os
    prev = jax.config.jax_compilation_cache_dir
    assert prev in (None, os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = ops.use_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if env_dir is None:
            assert got == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_autotune_feasible_and_sane():
    from repro.core.autotune import pick, vmem_usage
    c = pick(4096, 8192, 4096)
    assert c.vmem_bytes <= 16 * 2 ** 20
    assert 4096 % c.block_m == 0 and 8192 % c.block_k == 0
    # tiny-M GEMM: one M block => "os" already fetches weights once
    c2 = pick(128, 8192, 8192)
    assert c2.schedule == "os" and c2.traffic_bytes < 1e9
    # huge-M, shallow-K GEMM: weight re-fetches dominate "os";
    # weight-stationary fetches W exactly once and must win
    c3 = pick(65536, 512, 8192)
    assert c3.schedule == "ws", c3
    from repro.core.autotune import traffic
    alt = traffic(65536, 512, 8192, c3.block_m, c3.block_k, c3.block_n, "os")
    assert c3.traffic_bytes < alt
