"""Infrastructure tests: optimizer, checkpoint, data, schedules, HLO
analysis, launch-step plumbing."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st  # guarded hypothesis import

from repro.optim import (AdamConfig, adam_init, adam_update,
                         block_quantize, block_dequantize,
                         clip_by_global_norm, schedule, sgd)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def _quadratic_params():
    return {"w": jnp.asarray([3.0, -2.0, 1.5]), "b": jnp.asarray([[1.0, -1.0]])}


@pytest.mark.parametrize("eightbit", [False, True])
def test_adam_minimizes_quadratic(eightbit):
    params = _quadratic_params()
    cfg = AdamConfig(lr=0.1, eightbit=eightbit, grad_clip=None)
    state = adam_init(params, cfg)

    def loss(p):
        return sum(jnp.sum(jnp.square(x))
                   for x in jax.tree_util.tree_leaves(p))

    @jax.jit
    def step(params, state):
        grads = jax.grad(loss)(params)
        return adam_update(grads, state, params, cfg)[:2]

    for _ in range(150):
        params, state = step(params, state)
    assert float(loss(params)) < 1e-2


def test_block_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 512)) * 3
    q = block_quantize(x)
    assert q.codes.dtype == jnp.int8 and q.codes.shape == x.shape
    err = jnp.abs(block_dequantize(q) - x)
    per_block_max = jnp.max(jnp.abs(x.reshape(8, 2, 256)), axis=-1)
    # symmetric int8: error <= scale/2 = amax/254
    assert float(err.max()) <= float(per_block_max.max()) / 127.0


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 7), st.integers(1, 600))
def test_prop_block_quantize_shapes(rows, cols):
    x = jax.random.normal(jax.random.PRNGKey(rows * cols), (rows, cols))
    q = block_quantize(x)
    out = block_dequantize(q)
    assert out.shape == x.shape
    amax = float(jnp.abs(x).max())
    assert float(jnp.abs(out - x).max()) <= amax / 127.0 + 1e-6


def test_8bit_adam_state_is_4x_smaller():
    params = {"w": jnp.zeros((1024, 256))}
    fp = adam_init(params, AdamConfig())
    q8 = adam_init(params, AdamConfig(eightbit=True))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))
    assert nbytes(fp.m) / nbytes(q8.m) > 3.0


def test_grad_clip():
    grads = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(grads, 1.0)
    np.testing.assert_allclose(norm, 20.0)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(clipped["a"])), 1.0, rtol=1e-5)


def test_sgd_momentum_descends():
    params = _quadratic_params()
    cfg = sgd.SGDConfig(lr=0.05, momentum=0.9)
    state = sgd.sgd_init(params, cfg)
    def loss(p):
        return sum(jnp.sum(jnp.square(x))
                   for x in jax.tree_util.tree_leaves(p))
    for _ in range(100):
        grads = jax.grad(loss)(params)
        params, state = sgd.sgd_update(grads, state, params, cfg)
    assert float(loss(params)) < 1e-3


def test_schedules():
    fn = schedule.warmup_cosine(10, 100)
    assert float(fn(jnp.asarray(0))) == 0.0
    assert abs(float(fn(jnp.asarray(10))) - 1.0) < 1e-6
    assert float(fn(jnp.asarray(100))) < 0.2
    eps = schedule.linear_epsilon(1.0, 0.1, 100)
    np.testing.assert_allclose(float(eps(jnp.asarray(50))), 0.55)


# ---------------------------------------------------------------------------
# Test-suite hygiene: collection must not depend on optional extras
# ---------------------------------------------------------------------------

def test_no_direct_hypothesis_imports_in_tests():
    """Tier-1 runs in minimal containers; every property test must import
    hypothesis through ``tests/hypcompat.py`` so collection stays clean
    when the package is absent (CI also enforces ``pytest --co -q``)."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    offenders = []
    for name in sorted(os.listdir(tests_dir)):
        if not name.endswith(".py") or name == "hypcompat.py":
            continue
        with open(os.path.join(tests_dir, name)) as f:
            for lineno, line in enumerate(f, 1):
                stripped = line.strip()
                if (stripped.startswith("import hypothesis")
                        or stripped.startswith("from hypothesis")):
                    offenders.append(f"{name}:{lineno}: {stripped}")
    assert not offenders, (
        "direct hypothesis imports found (route them through hypcompat):\n"
        + "\n".join(offenders))


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    from repro import checkpoint as ck
    tree = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
            "step": jnp.asarray(7, jnp.int32)}
    path = ck.save_checkpoint(str(tmp_path), tree, step=7)
    assert ck.latest_step(str(tmp_path)) == 7
    loaded = ck.load_checkpoint(path, tree)
    np.testing.assert_allclose(loaded["params"]["w"], tree["params"]["w"])
    assert int(loaded["step"]) == 7


def test_checkpoint_quantized_params(tmp_path):
    from repro import checkpoint as ck
    from repro.core import ptq
    from repro.core.qconfig import QuantConfig
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 16))}
    packed = ptq.ptq_pack(params, QuantConfig.ptq_int(8))
    path = ck.save_checkpoint(str(tmp_path / "q.msgpack"), packed)
    loaded = ck.load_checkpoint(path, packed)
    np.testing.assert_allclose(ptq.ptq_unpack(loaded)["w"],
                               ptq.ptq_unpack(packed)["w"])
    # on-disk artifact carries the ~4x reduction
    assert os.path.getsize(path) < 16 * 16 * 4 * 2


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_dataset_learnable_structure():
    from repro.data import SyntheticLMDataset
    ds = SyntheticLMDataset(vocab=64, seq_len=32, batch=4, seed=0)
    b1 = next(ds.batches())
    assert b1["tokens"].shape == (4, 32)
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # markov structure: every successor must be in the transition table
    succ = ds._succ
    ok = [b1["labels"][i, t] in succ[b1["tokens"][i, t]]
          for i in range(4) for t in range(31)]
    assert all(ok)


def test_sharded_batcher_no_mesh():
    from repro.data import ShardedBatcher
    sb = ShardedBatcher(None)
    out = sb.put({"tokens": np.zeros((4, 8), np.int32)})
    assert out["tokens"].shape == (4, 8)


# ---------------------------------------------------------------------------
# HLO analysis unit tests
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
HloModule test

%cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]{0}) parameter(0)
  %c = s32[] constant(6)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %ag = f32[4]{0} all-gather(%x), replica_groups={}, dimensions={0}
  ROOT %t = (s32[], f32[4]{0}) tuple(%i, %ag)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %ar = f32[2,8]{1,0} all-reduce(%a), to_apply=%add
  %w = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body
  ROOT %r = f32[4]{0} get-tuple-element(%w), index=1
}
"""


def test_hlo_collective_trip_weighting():
    from repro.launch import hlo_analysis as H
    stats = H.collective_stats(HLO_SAMPLE)
    # all-reduce f32[2,8] once = 64B; all-gather f32[4] x6 trips = 96B
    assert stats["all-reduce"] == 64.0
    assert stats["all-gather"] == 6 * 16.0
    assert stats["total"] == 64.0 + 96.0


def test_hlo_memory_summary():
    from repro.launch.hlo_analysis import summarize_memory

    class FakeMem:
        argument_size_in_bytes = 100.0
        output_size_in_bytes = 50.0
        temp_size_in_bytes = 200.0
        generated_code_size_in_bytes = 1.0
        alias_size_in_bytes = 50.0
    out = summarize_memory(FakeMem())
    assert out["total_nonalias_bytes"] == 300.0


# ---------------------------------------------------------------------------
# Launch steps (local, no production mesh)
# ---------------------------------------------------------------------------

def test_input_specs_all_arch_shape_pairs():
    from repro.configs import base as cfgs
    from repro.launch import steps
    for arch in cfgs.names():
        cfg = cfgs.get(arch)
        for shape in cfgs.INPUT_SHAPES.values():
            cfg2, variant = steps.resolve_arch_for_shape(cfg, shape)
            specs = steps.input_specs(cfg2, shape)
            assert "tokens" in specs
            if shape.kind == "decode":
                assert specs["tokens"].shape[1] == 1
            else:
                assert specs["tokens"].shape == (shape.global_batch,
                                                 shape.seq_len)
            if shape.name == "long_500k" and not cfg.supports_long_500k:
                assert variant == "swa-variant"
                assert cfg2.long_context_window is not None


def test_analytic_flops_sane():
    from repro.configs import base as cfgs
    from repro.launch import analytic
    cfg = cfgs.get("stablelm-12b")
    shape = cfgs.INPUT_SHAPES["train_4k"]
    got = analytic.step_flops(cfg, shape)
    model = analytic.model_flops(cfg, shape)
    # train step ~ 2x the 6ND number (remat + attention) — same decade
    assert 0.8 * model < got < 4.0 * model
    # decode flops are tiny vs train
    dec = analytic.step_flops(cfg, cfgs.INPUT_SHAPES["decode_32k"])
    assert dec < got / 1000


def test_make_host_mesh_and_train_step_local():
    """One real train step through the launcher plumbing on CPU."""
    from repro.configs import base as cfgs
    from repro.launch import steps as steps_lib
    from repro.models import transformer
    from repro.optim import adam as adam_lib

    cfg = cfgs.get_reduced("h2o-danube-1.8b")
    train_step, adam_cfg = steps_lib.make_train_step(cfg)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    opt = adam_lib.adam_init(params, adam_cfg)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    params2, opt2, qat, metrics = jax.jit(train_step)(params, opt, batch, {})
    assert jnp.isfinite(metrics["loss"])
    assert int(opt2.step) == 1


@pytest.mark.slow
def test_dryrun_subprocess_end_to_end():
    """The real dry-run entry point: 512 fake devices, lower+compile."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm-125m",
         "--shape", "decode_32k", "--out", "/tmp/test_dryrun"],
        capture_output=True, text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "All dry-runs compiled successfully" in out.stdout


# ---------------------------------------------------------------------------
# Persistent compilation cache placement (entry points)
# ---------------------------------------------------------------------------

def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
