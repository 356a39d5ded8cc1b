"""Training substrate: optimizers, checkpointing, fault recovery, microbatch
equivalence, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.train import checkpoint as ckpt
from repro.train.compress import dequantize_int8, quantize_int8
from repro.train.fault import FailureInjector, Watchdog, run_with_recovery
from repro.train.optim import Schedule, adafactor, adamw, make_optimizer
from repro.train.step import init_state, make_train_step

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def _quad_problem():
    params = {"w": jnp.asarray([2.0, -3.0, 1.5]), "b": jnp.asarray([[1.0, -1.0]] * 64)}
    axes = {"w": (None,), "b": (None, None)}
    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)
    return params, axes, loss


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizers_descend_quadratic(name):
    params, axes, loss = _quad_problem()
    opt = make_optimizer(name, Schedule(peak_lr=0.05, warmup_steps=1,
                                        decay_steps=100))
    state, _ = opt.init(params, axes)
    l0 = float(loss(params))
    for step in range(50):
        grads = jax.grad(loss)(params)
        params, state, m = opt.update(grads, state, params,
                                      jnp.asarray(step, jnp.int32))
    assert float(loss(params)) < 0.2 * l0
    assert np.isfinite(float(m["grad_norm"]))


def test_adafactor_state_is_factored():
    params = {"big": jnp.zeros((256, 512)), "small": jnp.zeros((4, 8)),
              "vec": jnp.zeros((300,))}
    axes = {"big": ("embed", "mlp"), "small": (None, None), "vec": (None,)}
    opt = adafactor(Schedule())
    state, state_axes = opt.init(params, axes)
    assert set(state["big"]) == {"vr", "vc"}
    assert state["big"]["vr"].shape == (256,)
    assert state["big"]["vc"].shape == (512,)
    assert set(state["small"]) == {"v"}          # too small to factor
    assert set(state["vec"]) == {"v"}
    assert state_axes["big"]["vr"] == ("embed",)
    assert state_axes["big"]["vc"] == ("mlp",)
    # factored state is ~O(n+m), not O(nm)
    big_state = state["big"]["vr"].size + state["big"]["vc"].size
    assert big_state < params["big"].size / 100


def test_schedule_warmup_and_decay():
    s = Schedule(peak_lr=1e-3, warmup_steps=10, decay_steps=100, min_ratio=0.1)
    assert float(s(jnp.asarray(0))) < 2e-4
    assert float(s(jnp.asarray(9))) == pytest.approx(1e-3, rel=1e-3)
    assert float(s(jnp.asarray(1000))) == pytest.approx(1e-4, rel=1e-2)


# ---------------------------------------------------------------------------
# Microbatch accumulation
# ---------------------------------------------------------------------------

def test_microbatch_equals_full_batch():
    cfg = get_config("paper-tiny").smoke()
    state, _ = init_state(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab)
    batch = {"tokens": toks}
    s1, m1 = jax.jit(make_train_step(cfg))(state, batch)
    s2, m2 = jax.jit(make_train_step(cfg, num_microbatches=2))(state, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    d = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                           b.astype(jnp.float32)))),
        s1["params"], s2["params"])
    assert max(jax.tree_util.tree_leaves(d)) < 2e-5


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_rotation(tmp_path):
    cfg = get_config("paper-tiny").smoke()
    state, _ = init_state(KEY, cfg)
    d = str(tmp_path)
    for step in (5, 10, 15, 20):
        state = {**state, "step": jnp.asarray(step, jnp.int32)}
        ckpt.save(state, d, step, keep=2)
    assert ckpt.latest_step(d) == 20
    assert sorted(os.listdir(d)) == ["step_00000015", "step_00000020"]
    restored, got_step = ckpt.restore(state, d)
    assert got_step == 20
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async_and_atomicity(tmp_path):
    cfg = get_config("paper-tiny").smoke()
    state, _ = init_state(KEY, cfg)
    t = ckpt.save_async(state, str(tmp_path), 7)
    t.join()
    assert ckpt.latest_step(str(tmp_path)) == 7
    assert not any(x.endswith(".tmp") for x in os.listdir(tmp_path))


def test_checkpoint_ignores_partial_tmp(tmp_path):
    os.makedirs(tmp_path / "step_00000099.tmp")
    assert ckpt.latest_step(str(tmp_path)) is None


def test_checkpoint_restore_detects_flipped_byte(tmp_path):
    """Per-leaf crc32: a single flipped payload byte rides clean through
    the shape/dtype asserts but must raise the typed DataCorruption."""
    from repro.train.fault import DataCorruption
    state = {"w": jnp.arange(16, dtype=jnp.float32), "b": jnp.zeros(4)}
    ckpt.save(state, str(tmp_path), 3)
    victim = tmp_path / "step_00000003" / "w.npy"
    raw = bytearray(victim.read_bytes())
    raw[-1] ^= 0xFF                       # payload, not header
    victim.write_bytes(bytes(raw))
    with pytest.raises(DataCorruption, match="crc32"):
        ckpt.restore(state, str(tmp_path))
    # pre-crc checkpoints (no crc32 key in meta) still load unverified
    import json
    meta_p = tmp_path / "step_00000003" / "meta.json"
    meta = json.loads(meta_p.read_text())
    for e in meta["leaves"]:
        e.pop("crc32", None)
    meta_p.write_text(json.dumps(meta))
    restored, step = ckpt.restore(state, str(tmp_path))
    assert step == 3


def test_checkpoint_ignores_torn_meta(tmp_path):
    # rename happened but meta.json is torn/unreadable: not a restorable
    # checkpoint, latest_step must fall back to the previous good one
    state = {"w": jnp.arange(4)}
    ckpt.save(state, str(tmp_path), 3)
    os.makedirs(tmp_path / "step_00000009")
    (tmp_path / "step_00000009" / "meta.json").write_text("{not json")
    assert ckpt.latest_step(str(tmp_path)) == 3


def test_checkpoint_flush_joins_async_writers(tmp_path):
    state = {"w": jnp.arange(8), "b": jnp.ones((3,))}
    for step in (1, 2, 3):
        ckpt.save_async(state, str(tmp_path), step)
    ckpt.flush()                      # shutdown barrier: nothing dropped
    assert ckpt.latest_step(str(tmp_path)) == 3
    assert not any(x.endswith(".tmp") for x in os.listdir(tmp_path))


def test_checkpoint_rewrite_clears_stale_tmp(tmp_path):
    # a crash left a half-written tmp for the SAME step; the rewrite must
    # not inherit its leaves
    stale = tmp_path / "step_00000005.tmp"
    os.makedirs(stale)
    (stale / "zombie.npy").write_bytes(b"junk")
    ckpt.save({"w": jnp.arange(4)}, str(tmp_path), 5)
    assert ckpt.latest_step(str(tmp_path)) == 5
    assert not stale.exists()
    assert "zombie.npy" not in os.listdir(tmp_path / "step_00000005")


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------

def test_recovery_replays_from_checkpoint(tmp_path):
    log = []
    box = {"step": 0, "saved": 0}

    def one(step):
        log.append(step)
        box["step"] = step + 1
        return {}

    def save(step):
        box["saved"] = step

    def restore():
        return box["saved"]

    inj = FailureInjector(fail_at_steps=(7, 13))
    res = run_with_recovery(one, save, restore, n_steps=20, ckpt_every=5,
                            injector=inj)
    assert res["final_step"] == 20
    assert res["restarts"] == 2
    # steps 5..6 replayed after the failure at 7 (restore to ckpt@5)
    assert log.count(5) >= 2
    assert sorted(set(log)) == list(range(20))


def test_failure_injector_fail_kinds():
    from repro.train.fault import InjectedFailure, ProbeTimeout, WorkerCrash
    inj = FailureInjector(fail_at_steps=(3,), fail_kinds={5: ProbeTimeout,
                                                          7: WorkerCrash})
    with pytest.raises(ProbeTimeout):
        inj.maybe_fail(5)
    inj.maybe_fail(5)                          # fail-once: replay proceeds
    with pytest.raises(WorkerCrash):
        inj.maybe_fail(7)
    with pytest.raises(InjectedFailure) as ei:  # generic kind preserved
        inj.maybe_fail(3)
    assert type(ei.value) is InjectedFailure
    inj.maybe_fail(4)                          # unscripted step: silent


def test_recovery_handles_typed_failures(tmp_path):
    from repro.train.fault import SnapshotInterrupt, WorkerCrash
    box = {"saved": 0}
    inj = FailureInjector(fail_kinds={2: WorkerCrash, 6: SnapshotInterrupt})
    res = run_with_recovery(lambda s: {}, lambda s: box.update(saved=s),
                            lambda: box["saved"], n_steps=10, ckpt_every=2,
                            injector=inj)
    assert res["final_step"] == 10
    assert res["restarts"] == 2


def test_watchdog_flags_stragglers():
    import time
    w = Watchdog(factor=3.0, warmup=3)
    for i in range(10):
        w.start()
        time.sleep(0.02 if i != 7 else 0.2)
        w.stop(i)
    assert 7 in w.stragglers
    assert len(w.stragglers) <= 2


def test_watchdog_window_flags_by_count_and_forgets():
    """The windowed feed a DedupService gives each worker: median + MAD
    over the last ``window`` durations only, both histories trimmed."""
    w = Watchdog(factor=3.0, warmup=4, window=8)
    flagged = []
    for i in range(40):                   # every fourth call is slow
        slow = i % 4 == 3
        breach = w.observe(1.0 if slow else 0.01, i)
        assert breach == (slow and i >= 4), i
        if breach:
            flagged.append(i)
    assert flagged == list(range(7, 40, 4))
    assert len(w.times) == 8
    assert w.stragglers == flagged[-8:]
    # a worker that stays slow stops being an outlier once the window has
    # forgotten its fast calls
    verdicts = [w.observe(1.0, 40 + i) for i in range(8)]
    assert verdicts[0] and not verdicts[-1]
    assert w.times == [1.0] * 8


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_int8_quantization_unbiased_and_bounded():
    x = jax.random.normal(KEY, (4096,)) * 0.01
    errs = []
    acc = jnp.zeros_like(x)
    n = 64
    for i in range(n):
        q, s = quantize_int8(x, jax.random.PRNGKey(i))
        deq = dequantize_int8(q, s)
        errs.append(float(jnp.abs(deq - x).max()))
        acc = acc + deq
    scale = float(jnp.abs(x).max()) / 127.0
    assert max(errs) <= scale + 1e-9          # error < 1 quantization step
    bias = float(jnp.abs(acc / n - x).mean())
    assert bias < scale / np.sqrt(n) * 3       # stochastic rounding ~unbiased
