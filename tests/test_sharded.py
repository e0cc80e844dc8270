"""Sharded behaviour (subprocesses with 8 fake devices): presto vs disagg
vs hybrid placement collectives, compressed train step, row-sharded embedding
bag, context-parallel decode attention."""

import pytest

from conftest import run_sharded


def test_presto_zero_collectives_disagg_permutes():
    out = run_sharded("""
import jax, numpy as np, jax.numpy as jnp
from repro.core.spec import TransformSpec
from repro.core.presto import PreStoEngine
from repro.core.preprocess import pages_from_partition
from repro.data.synth import RMDataConfig, SyntheticRecSysSource
from repro.launch.mesh import make_mesh
cfg = RMDataConfig("t", 4, 3, 4, 8, 2, 32, 1 << 16, 1024, rows_per_partition=256)
src = SyntheticRecSysSource(cfg, rows=256)
spec = TransformSpec.from_source(src)
mesh = make_mesh((4, 2), ("data", "model"))
pages = {k: jnp.asarray(v) for k, v in pages_from_partition(src.partition(0), spec).items()}
ep = PreStoEngine(spec, mesh, placement="presto")
ed = PreStoEngine(spec, mesh, placement="disagg")
mp = ep.jit_preprocess()(pages)
md = ed.jit_preprocess()(pages)
for k in mp:
    assert np.array_equal(np.asarray(mp[k]), np.asarray(md[k])), k
tp = jax.jit(ep.preprocess_global).lower(pages).compile().as_text()
td = jax.jit(ed.preprocess_global).lower(pages).compile().as_text()
from repro.launch.hlo_cost import analyze
cp, cd = analyze(tp), analyze(td)
assert cp.coll_bytes == 0, f"presto must move zero bytes, got {cp.coll_bytes}"
assert cd.coll_breakdown["collective-permute"] > 0, "disagg must permute"
print("PRESTO_COLL", cp.coll_bytes, "DISAGG_COLL", cd.coll_bytes)
""")
    assert "PRESTO_COLL 0" in out


def test_hybrid_collectives_only_for_host_families():
    """Hybrid placement must permute exactly the host-placed families'
    pages + outputs — nothing more (ISP families stay collective-free)."""
    out = run_sharded("""
import jax, numpy as np, jax.numpy as jnp
from repro.core import opgraph
from repro.core.spec import TransformSpec
from repro.core.presto import PreStoEngine
from repro.core.preprocess import pages_from_partition
from repro.data.synth import RMDataConfig, SyntheticRecSysSource
from repro.launch.hlo_cost import analyze
from repro.launch.mesh import make_mesh
cfg = RMDataConfig("t", 4, 3, 4, 8, 2, 32, 1 << 16, 1024, rows_per_partition=256)
src = SyntheticRecSysSource(cfg, rows=256)
spec = TransformSpec.from_source(src)
rows = 256
mesh = make_mesh((4, 2), ("data", "model"))
n_data = 4
pages = {k: jnp.asarray(v) for k, v in pages_from_partition(src.partition(0), spec).items()}
host_fams = ("gen", "lengths")
eh = PreStoEngine(spec, mesh,
                  placement={f: "host" for f in host_fams})
assert eh.placement == "hybrid" and eh.host_families() == host_fams
ep = PreStoEngine(spec, mesh, placement="presto")
mh = eh.jit_preprocess()(pages)
mp = ep.jit_preprocess()(pages)
for k in mh:
    assert np.array_equal(np.asarray(mh[k]), np.asarray(mp[k])), k
th = jax.jit(eh.preprocess_global).lower(pages).compile().as_text()
ch = analyze(th)
page_b = opgraph.family_page_bytes(spec, rows)
out_b = opgraph.family_batch_bytes(spec, rows)
expected = sum((page_b[f] + out_b[f]) // n_data for f in host_fams)
got = ch.coll_breakdown.get("collective-permute", 0)
assert got == expected, (got, expected)
assert ch.coll_bytes == got, "hybrid must emit no collectives beyond the host-family permutes"
# all-ISP "hybrid" degenerates to zero collectives
e0 = PreStoEngine(spec, mesh, placement={})
t0 = jax.jit(e0.preprocess_global).lower(pages).compile().as_text()
assert analyze(t0).coll_bytes == 0
print("HYBRID_PERMUTE_BYTES", got, "EXPECTED", expected)
""")
    assert "HYBRID_PERMUTE_BYTES" in out


def test_compressed_train_step_int8_collectives():
    out = run_sharded("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.models.config import ModelConfig
from repro.models import transformer as T
from repro.distributed.sharding import ShardingRules
from repro.train import adamw, warmup_cosine, make_train_step, make_compressed_train_step
from repro.train.compression import init_error_state
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules_inner = ShardingRules.make(mesh, overrides={"batch": ("data",)})
cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab_size=256, dtype="float32", remat="none")
opt = adamw(warmup_cosine(1e-3, 5, 50))
loss_inner = lambda p, b: T.loss_fn(p, b, cfg, rules_inner)
params = T.init_params(jax.random.PRNGKey(0), cfg)
state = {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32),
         "err": init_error_state(params)}
batch = {"tokens": jnp.ones((8, 64), jnp.int32), "labels": jnp.ones((8, 64), jnp.int32),
         "mask": jnp.ones((8, 64), jnp.float32)}
bspec = lambda b: {k: P("pod") if v.ndim == 1 else P("pod", None) for k, v in b.items()}
cstep = jax.jit(make_compressed_train_step(loss_inner, opt, mesh, bspec))
s1, m1 = cstep(state, batch)
s2, m2 = cstep(s1, batch)
assert float(m2["loss"]) < float(m1["loss"])
txt = cstep.lower(state, batch).compile().as_text()
# the cross-pod hop must carry int8: the all-gather's operand is s8
n_s8 = sum(1 for l in txt.splitlines()
           if "s8" in l and ("all-gather" in l or "all-reduce" in l))
assert n_s8 > 0
# compressed step tracks an uncompressed step closely after one update
step = jax.jit(make_train_step(loss_inner, opt))
su, _ = step({k: state[k] for k in ("params", "opt", "step")}, batch)
import numpy as np
diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     s1["params"], su["params"])
md = max(jax.tree_util.tree_leaves(diffs))
assert md < 1e-3, md
print("INT8_AG", n_s8, "MAXDIFF", md)
""")
    assert "INT8_AG" in out


def test_rowsharded_embedding_matches_local():
    out = run_sharded("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs.registry import get_recsys
from repro.distributed.sharding import ShardingRules
from repro.models import recsys as RS
from repro.launch.mesh import make_mesh
rcfg = get_recsys("rm1", reduced=True)
mesh = make_mesh((2, 4), ("data", "model"))
rules_m = ShardingRules.make(mesh)
rules_l = ShardingRules.make(None)
params = RS.init_params(jax.random.PRNGKey(0), rcfg)
rng = np.random.default_rng(0)
B, S, L, G = 16, rcfg.data.n_sparse, rcfg.data.max_sparse_len, rcfg.data.n_generated
mids = jnp.asarray(rng.integers(0, rcfg.data.embedding_rows, (B, S, L)), jnp.int32)
lens = jnp.asarray(rng.integers(1, L + 1, (B, S)), jnp.int32)
oids = jnp.asarray(rng.integers(0, rcfg.data.embedding_rows, (B, G)), jnp.int32)
local = RS.embedding_bag(params["tables"], mids, lens, oids, rcfg, rules_l)
sharded = jax.jit(lambda t: RS.embedding_bag(t, mids, lens, oids, rcfg, rules_m))(params["tables"])
np.testing.assert_allclose(np.asarray(local), np.asarray(sharded), rtol=2e-5, atol=2e-5)
print("EMB_OK")
""")
    assert "EMB_OK" in out


def test_cp_decode_attention_matches_plain():
    out = run_sharded("""
import jax, numpy as np, jax.numpy as jnp
from repro.models.layers import decode_attention, cp_decode_attention
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(0)
B, S, K, G, D = 1, 256, 2, 4, 16
q = jnp.asarray(rng.normal(size=(B, 1, K * G, D)), jnp.float32)
kc = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
vc = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
clen = jnp.full((B,), 100, jnp.int32)
plain = decode_attention(q, kc, vc, clen)
cp = jax.jit(lambda q, k, v, n: cp_decode_attention(q, k, v, n, mesh=mesh, axis="data"))(q, kc, vc, clen)
np.testing.assert_allclose(np.asarray(plain), np.asarray(cp), rtol=1e-5, atol=1e-5)
print("CP_OK")
""")
    assert "CP_OK" in out
