"""Distribution machinery: HLO cost analyzer, spec selection, small-mesh
end-to-end sharded round, and a subprocess dry-run on a tiny forced mesh."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.models.sharding import MeshAxes
from repro.models.specs import ShardingCtx, pad_vocab
from repro.utils.hlo_cost import analyze_hlo
from repro.utils.roofline import Roofline, model_flops

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# HLO cost analyzer
# ---------------------------------------------------------------------------


def test_analyzer_counts_scan_trips():
    L, N = 8, 128

    def step(w, x):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        return jax.lax.scan(body, x, w)[0]

    c = jax.jit(step).lower(
        jax.ShapeDtypeStruct((L, N, N), jnp.float32),
        jax.ShapeDtypeStruct((4, N), jnp.float32)).compile()
    cost = analyze_hlo(c.as_text())
    assert cost.flops == pytest.approx(2 * 4 * N * N * L, rel=0.01)


def test_analyzer_counts_backward_three_matmuls():
    L, N = 4, 64

    def step(w, x):
        def loss(w_):
            def body(c, wl):
                return jnp.tanh(c @ wl), None
            return jnp.sum(jax.lax.scan(body, x, w_)[0] ** 2)
        return jax.grad(loss)(w)

    c = jax.jit(step).lower(
        jax.ShapeDtypeStruct((L, N, N), jnp.float32),
        jax.ShapeDtypeStruct((2, N), jnp.float32)).compile()
    cost = analyze_hlo(c.as_text())
    # fwd + dgrad + wgrad = 3 matmuls per layer
    assert cost.flops == pytest.approx(3 * 2 * 2 * N * N * L, rel=0.05)


def test_analyzer_bytes_reasonable():
    def f(a, b):
        return a @ b

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
    cost = analyze_hlo(c.as_text())
    lo = 3 * 256 * 256 * 4          # two reads + one write
    assert lo <= cost.bytes <= 4 * lo


# ---------------------------------------------------------------------------
# Roofline math
# ---------------------------------------------------------------------------


def test_roofline_terms_and_bottleneck():
    r = Roofline(flops=197e12, bytes_hbm=819e9 / 2, bytes_wire=0.0,
                 chips=256, model_flops=197e12 * 256)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(0.5)
    assert r.bottleneck == "compute"
    assert r.useful_ratio == pytest.approx(1.0)


def test_model_flops_train_vs_decode():
    from repro.configs import get_config, get_shape
    cfg = get_config("qwen2-0.5b")
    n = 500_000_000
    tr = model_flops(cfg, get_shape("train_4k"), n)
    de = model_flops(cfg, get_shape("decode_32k"), n)
    assert tr == pytest.approx(6 * n * 256 * 4096)
    assert de == pytest.approx(2 * n * 128)


def test_moe_active_params():
    from repro.configs import get_config
    from repro.utils.roofline import active_params
    cfg = get_config("qwen3-moe-30b-a3b")
    total = 30_000_000_000
    act = active_params(cfg, total)
    assert act < 0.2 * total  # top-8 of 128 experts


# ---------------------------------------------------------------------------
# Spec selection
# ---------------------------------------------------------------------------


class _FakeCtx(ShardingCtx):
    def __init__(self, model_size=16, data_size=16, fsdp=True):
        self.mesh = object()
        self.axes = MeshAxes()
        self.model_size = model_size
        self.data_size = data_size
        self.fsdp = fsdp


def test_attn_spec_picker_prefers_divisible_axes():
    ctx = _FakeCtx()
    # granite: kv=1, G=48, hd=128 -> shard G
    assert ctx.attn_q_spec(1, 48, 128) == P("data", None, "model", None)
    # qwen2-7b: kv=4, G=7, hd=128 -> shard hd
    assert ctx.attn_q_spec(4, 7, 128) == P("data", None, None, "model")
    # zamba2: kv=32 -> shard kv heads
    assert ctx.attn_q_spec(32, 1, 64) == P("data", "model", None, None)


def test_vocab_padding():
    assert pad_vocab(49152) == 49152         # already a multiple of 512
    assert pad_vocab(151936) == 152064
    assert pad_vocab(256206) == 256512       # seamless's awkward vocab


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "qwen3-moe-30b-a3b",
                                  "xlstm-1.3b", "zamba2-1.2b",
                                  "llama-3.2-vision-11b",
                                  "seamless-m4t-medium"])
def test_param_specs_match_params(arch):
    """Every param leaf has a spec with matching rank and divisible dims."""
    from repro.configs import get_config
    from repro.models.api import build_model
    cfg = get_config(arch)
    model = build_model(cfg)
    ctx = _FakeCtx()
    params_abs = model.abstract_params()
    specs = model.param_specs(ctx)
    flat_p = jax.tree_util.tree_leaves(params_abs)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    sizes = {"data": 16, "model": 16}
    for leaf, spec in zip(flat_p, flat_s, strict=True):
        assert len(spec) <= leaf.ndim, (leaf.shape, spec)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim,
                           strict=False):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            factor = int(np.prod([sizes[a] for a in axes]))
            assert dim % factor == 0, (leaf.shape, spec)


# ---------------------------------------------------------------------------
# Small-mesh end-to-end (8 forced host devices in a subprocess)
# ---------------------------------------------------------------------------

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_reduced
from repro.launch.mesh import make_host_mesh
from repro.models.api import build_model
from repro.models.specs import ShardingCtx
from repro.federated.rounds import make_fl_round
from repro.optim import sgd

mesh = make_host_mesh(4, 2)
cfg = get_reduced("qwen2-0.5b").with_(dtype="float32", remat=False,
                                      d_model=256, num_heads=4, num_kv_heads=2)
ctx = ShardingCtx(mesh)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = sgd(0.1)
key = jax.random.PRNGKey(1)
B, S, N = 8, 16, 4
batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
         "client_ids": jnp.repeat(jnp.arange(N), B // N)}
mask = jnp.array([1., 0., 1., 0.])

# sharded round
pspecs = model.param_specs(ctx)
named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                               is_leaf=lambda x: isinstance(x, P))
ps = jax.device_put(params, named(pspecs))
st = opt.init(ps)
rnd = make_fl_round(model, opt, N, 2, noise_std=0.0, ctx=ctx)
with mesh:
    p_sh, _, m_sh = jax.jit(rnd)(ps, st, batch, mask, key)

# unsharded reference
rnd0 = make_fl_round(model, opt, N, 2, noise_std=0.0, ctx=None)
p_ref, _, m_ref = jax.jit(rnd0)(params, opt.init(params), batch, mask, key)

np.testing.assert_allclose(float(m_sh.loss), float(m_ref.loss), rtol=1e-4)
np.testing.assert_allclose(np.asarray(m_sh.client_losses),
                           np.asarray(m_ref.client_losses), rtol=1e-3)
for a, b in zip(jax.tree_util.tree_leaves(p_sh),
                jax.tree_util.tree_leaves(p_ref)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=5e-4)
print("SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_round_matches_unsharded():
    """The 4x2-mesh FL round reproduces the single-device round exactly —
    proves the sharding (specs + constraints) does not change semantics."""
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT],
        capture_output=True, text=True, timeout=540,
        env={**os.environ, "PYTHONPATH": str(REPO / "src")},
        cwd=str(REPO))
    assert "SHARDED_OK" in res.stdout, res.stderr[-3000:]
