"""Distribution substrate: sharding rule engine, data determinism,
checkpoint atomicity/resume, optimizer math."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.ckpt.checkpoint import Checkpointer
from repro.data.pipeline import DataConfig, get_batch
from repro.sharding import partition
from repro.train import optim, step as step_lib


# --------------------------------------------------------------------------
# sharding rules
# --------------------------------------------------------------------------

class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 8}


def test_resolve_spec_basic():
    rules = {"vocab": ("model",), "embed": ("data",), "ff": ("model",)}
    ps = partition.resolve_spec(("vocab", "embed"), (1600, 512), FakeMesh(),
                                rules)
    assert ps == P("model", "data")


def test_resolve_spec_divisibility_fallback():
    rules = {"vocab": ("model",), "embed": ("data",)}
    ps = partition.resolve_spec(("vocab", "embed"), (1601, 512), FakeMesh(),
                                rules)
    assert ps == P(None, "data")


def test_resolve_spec_single_use_rail():
    rules = {"a": ("model",), "b": ("model",)}
    ps = partition.resolve_spec(("a", "b"), (64, 64), FakeMesh(), rules)
    assert ps == P("model")          # second "model" use falls to None


def test_batch_pspec_fallback_for_tiny_batch():
    assert partition.batch_pspec(FakeMesh(), 1) == P()       # 1 % 4 != 0
    assert partition.batch_pspec(FakeMesh(), 8) == P("data")


def test_state_shardings_cover_all_leaves():
    cfg = configs.get_smoke("llama3_2_1b")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh, shapes = step_lib.state_shardings(cfg, mesh)
    n_sh = len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))
    n_shapes = len(jax.tree.leaves(shapes))
    assert n_sh == n_shapes


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------

def test_data_deterministic_and_shifted():
    dc = DataConfig(vocab=128, seq_len=32, global_batch=4, seed=7)
    a = get_batch(dc, step=5)
    b = get_batch(dc, step=5)
    c = get_batch(dc, step=6)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    assert (np.asarray(a["labels"][:, -1]) == -1).all()


def test_data_shards_disjoint_streams():
    dc = DataConfig(vocab=128, seq_len=16, global_batch=8, seed=0,
                    n_shards=2)
    s0 = get_batch(dc, 0, shard=0)
    s1 = get_batch(dc, 0, shard=1)
    assert s0["tokens"].shape == (4, 16)
    assert not np.array_equal(s0["tokens"], s1["tokens"])


# --------------------------------------------------------------------------
# checkpointing
# --------------------------------------------------------------------------

def test_ckpt_roundtrip_and_gc(tmp_path):
    cfg = configs.get_smoke("smollm_360m")
    state = step_lib.init_state(cfg, jax.random.key(0))
    ck = Checkpointer(tmp_path, keep=2)
    for s in (1, 2, 3):
        ck.save(state, s)
    assert sorted(ck.all_steps()) == [2, 3]          # GC keeps last 2
    restored, step = ck.restore(state)
    assert step == 3
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)


def test_ckpt_atomic_no_partial(tmp_path):
    """tmp dirs never count as checkpoints."""
    ck = Checkpointer(tmp_path)
    (tmp_path / "tmp.99").mkdir()
    assert ck.latest_step() is None


def test_resume_replays_identically(tmp_path):
    """train k steps, checkpoint, train k more — must equal 2k straight."""
    cfg = dataclasses.replace(configs.get_smoke("llama3_2_1b"))
    dc = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4, seed=3)
    ts = jax.jit(step_lib.make_train_step(cfg))

    state = step_lib.init_state(cfg, jax.random.key(1))
    for s in range(6):
        state, _ = ts(state, get_batch(dc, s))
    straight = state

    state = step_lib.init_state(cfg, jax.random.key(1))
    ck = Checkpointer(tmp_path)
    for s in range(3):
        state, _ = ts(state, get_batch(dc, s))
    ck.save(state, 3)
    resumed, start = ck.restore(state)
    for s in range(start, 6):
        resumed, _ = ts(resumed, get_batch(dc, s))

    d = jax.tree.map(lambda a, b: float(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
        straight["params"], resumed["params"])
    assert max(jax.tree.leaves(d)) < 1e-5


# --------------------------------------------------------------------------
# optimizer
# --------------------------------------------------------------------------

def test_adamw_matches_reference_update():
    cfg = optim.AdamWConfig(lr=1e-2, b1=0.9, b2=0.99, eps=1e-8,
                            weight_decay=0.0, grad_clip=0.0,
                            warmup_steps=0, total_steps=10**9)
    p = {"w": jnp.ones((3, 3)) * 2.0}
    g = {"w": jnp.ones((3, 3)) * 0.5}
    m = optim.init_moments(p)
    new_p, new_m, stats = optim.adamw_update(cfg, p, g, m, jnp.zeros((),
                                                                     jnp.int32))
    # step 1 bias-corrected adam with constant grad: update == lr * sign-ish
    mhat = 0.1 * 0.5 / (1 - 0.9)
    vhat = 0.01 * 0.25 / (1 - 0.99)
    expect = 2.0 - 1e-2 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]), expect, rtol=2e-5)


def test_grad_clip_caps_update_norm():
    cfg = optim.AdamWConfig(grad_clip=1.0, warmup_steps=0)
    p = {"w": jnp.zeros((4,))}
    g = {"w": jnp.full((4,), 100.0)}
    m = optim.init_moments(p)
    _, _, stats = optim.adamw_update(cfg, p, g, m, jnp.zeros((), jnp.int32))
    assert float(stats["grad_norm"]) == pytest.approx(200.0)


def test_lr_schedule_warmup_and_decay():
    cfg = optim.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110,
                            min_lr_ratio=0.1)
    lr0 = float(optim.lr_at(cfg, jnp.asarray(0)))
    lr5 = float(optim.lr_at(cfg, jnp.asarray(5)))
    lr10 = float(optim.lr_at(cfg, jnp.asarray(10)))
    lr110 = float(optim.lr_at(cfg, jnp.asarray(110)))
    assert lr0 == 0.0 and 0 < lr5 < lr10 <= 1.0
    assert lr110 == pytest.approx(0.1, abs=1e-3)


# --------------------------------------------------------------------------
# multi-device SPMD equivalence (subprocess with 8 host devices)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_equals_single_device(tmp_path):
    script = r"""
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import transformer
from repro.sharding import partition

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = configs.get_smoke("gemma2_9b")
params, specs = transformer.make_params(cfg, jax.random.key(0))
tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab)
ref = jax.jit(lambda p, t: transformer.forward(cfg, p, t, mode="train")[0])(params, tokens)
psh = partition.tree_shardings(specs, params, mesh)
params_s = jax.device_put(params, psh)
tok_s = jax.device_put(tokens, NamedSharding(mesh, P("data")))
with jax.set_mesh(mesh):
    out = jax.jit(lambda p, t: transformer.forward(
        cfg, p, t, mode="train", mesh=mesh)[0])(params_s, tok_s)
err = np.abs(np.float32(ref) - np.float32(out)).max()
assert err < 1e-1, err
print("SPMD-EQUAL", err)
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, cwd="/root/repo",
                       timeout=420)
    assert "SPMD-EQUAL" in r.stdout, r.stdout + r.stderr


def test_serve_rules_weights_stationary():
    """Decode ruleset: no FSDP contraction dim; experts 2-D sharded."""
    rules = partition.serve_rules(FakeMesh())
    assert rules["embed"] is None
    ps = partition.resolve_spec(("expert", "embed", "e_ff"),
                                (64, 512, 1408), FakeMesh(), rules)
    assert ps == P("model", None, "data")
