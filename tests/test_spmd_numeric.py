"""SPMD numerical correctness: sharded programs == single-device math.

jax locks the device count at first init, so these tests run in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 and a
(2, 2, 2) (pod, data, model) mesh, comparing against the local (mesh=None)
path. This is the numerical counterpart of the structural dry-run.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.distributed.partitioning import default_rules
from repro.models.common import MeshCtx, NULL_CTX, sharded_embedding_lookup, embedding_bag

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
ctx = MeshCtx(mesh=mesh, rules=default_rules(multi_pod=True))
rng = np.random.default_rng(0)

# --- sharded embedding lookup == local ---
tbl = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
ids = jnp.asarray(rng.integers(0, 64, (8, 5)), jnp.int32)
with mesh:
    out = jax.jit(lambda t, i: sharded_embedding_lookup(
        t, i, ctx, row_logical="table_rows", ids_logical=("batch", None),
        compute_dtype=jnp.float32))(tbl, ids)
ref = np.asarray(tbl)[np.asarray(ids)]
np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6)
print("lookup OK")

# --- embedding bag ---
lens = jnp.asarray(rng.integers(1, 6, (8,)), jnp.int32)
with mesh:
    bag = jax.jit(lambda t, i, l: embedding_bag(
        t, i, l, ctx, mode="mean", compute_dtype=jnp.float32))(tbl, ids, lens)
bag_ref = embedding_bag(tbl, ids, lens, NULL_CTX, compute_dtype=jnp.float32)
np.testing.assert_allclose(np.asarray(bag), np.asarray(bag_ref), rtol=1e-5)
print("bag OK")

# --- decode attention (seq-sharded cache) ---
from repro.models.transformer import attention as attn
b, kh, g, dh, smax = 4, 2, 2, 8, 16
h = kh * g
q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.float32)
kc = jnp.asarray(rng.normal(size=(b, smax, kh, dh)), jnp.float32)
vc = jnp.asarray(rng.normal(size=(b, smax, kh, dh)), jnp.float32)
kn = jnp.asarray(rng.normal(size=(b, kh, dh)), jnp.float32)
vn = jnp.asarray(rng.normal(size=(b, kh, dh)), jnp.float32)
cur = jnp.asarray(9, jnp.int32)
with mesh:
    out_s, k2s, v2s = jax.jit(lambda *a: attn.decode_attention(
        *a, ctx, "kv_seq"))(q, kc, vc, kn, vn, cur)
out_l, k2l, v2l = attn.decode_attention(q, kc, vc, kn, vn, cur, NULL_CTX)
np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_l), rtol=2e-5, atol=2e-5)
np.testing.assert_allclose(np.asarray(k2s), np.asarray(k2l), rtol=1e-6)
print("decode attn OK")

# --- MoE block (tokens sharded over all axes; experts over model) ---
from repro.configs.base import TransformerConfig
from repro.models.transformer import moe as moe_lib
cfg = TransformerConfig(name="m", family="moe", n_layers=1, d_model=16,
    n_heads=2, n_kv_heads=2, d_head=8, d_ff=8, vocab_size=64, n_experts=4,
    moe_top_k=2, capacity_factor=64.0, compute_dtype="float32")
t, d = 32, 16
x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
router = jnp.asarray(rng.normal(size=(d, 4)) * 0.3, jnp.float32)
wg = jnp.asarray(rng.normal(size=(4, d, 8)) * 0.2, jnp.float32)
wu = jnp.asarray(rng.normal(size=(4, d, 8)) * 0.2, jnp.float32)
wd_ = jnp.asarray(rng.normal(size=(4, 8, d)) * 0.2, jnp.float32)
with mesh:
    y_s, aux_s = jax.jit(lambda *a: moe_lib.moe_block(*a, cfg, ctx))(
        x, router, wg, wu, wd_)
y_l, aux_l = moe_lib.moe_block(x, router, wg, wu, wd_, cfg, NULL_CTX)
np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_l), rtol=5e-4, atol=5e-4)
print("moe OK")

# --- full tiny-LM train step: sharded loss == local loss ---
from repro.models.transformer import model as tm
cfg2 = TransformerConfig(name="t", family="dense", n_layers=2, d_model=32,
    n_heads=4, n_kv_heads=2, d_head=8, d_ff=64, vocab_size=199,
    compute_dtype="float32", param_dtype="float32", remat=True,
    scan_layers=True, kv_chunk=8, xent_chunk=8)
params = tm.init(cfg2, jax.random.PRNGKey(0))
batch = {"tokens": jnp.asarray(rng.integers(0, 199, (8, 16)), jnp.int32),
         "targets": jnp.asarray(rng.integers(0, 199, (8, 16)), jnp.int32)}
loss_l, _ = tm.loss_fn(params, batch, cfg2, NULL_CTX)
with mesh:
    loss_s, _ = jax.jit(lambda p, b: tm.loss_fn(p, b, cfg2, ctx))(params, batch)
np.testing.assert_allclose(float(loss_s), float(loss_l), rtol=2e-4)
print("lm loss OK", float(loss_l), float(loss_s))

# --- distributed search == local ---
from repro.search import search
qq = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
db = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
with mesh:
    vs, is_ = jax.jit(lambda a, b: search(a, b, 5, ctx))(qq, db)
vl, il = search(qq, db, 5, NULL_CTX)
np.testing.assert_array_equal(np.asarray(is_), np.asarray(il))
print("search OK")

# --- GNN full-batch aggregate == local ---
from repro.models.gnn import graphsage
n, e = 32, 96
hh = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)
src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
with mesh:
    agg_s = jax.jit(lambda *a: graphsage.mean_aggregate(*a, n, ctx))(hh, src, dst)
agg_l = graphsage.mean_aggregate(hh, src, dst, n, NULL_CTX)
np.testing.assert_allclose(np.asarray(agg_s), np.asarray(agg_l), rtol=1e-5, atol=1e-5)
print("gnn OK")
print("ALL SPMD NUMERIC OK")
"""


@pytest.mark.timeout(900)
def test_spmd_numeric_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=".",
                       capture_output=True, text=True, timeout=850)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "ALL SPMD NUMERIC OK" in r.stdout
