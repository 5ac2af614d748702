#!/usr/bin/env python
"""Chaos drill for the training resilience stack: kill saves at every
phase of the commit protocol, poison gradients on a schedule, and prove
no work is ever lost and no anomaly survives.

The operational twin of tests/test_checkpoint_manager.py and
tests/test_sentinel.py (docs/RESILIENCE.md "Checkpoint commit protocol" +
"Self-healing training"): eight scenarios arm ``paddle_tpu.faults``
injections against a real train loop —

1. crash matrix   — a seeded fault at EVERY save phase (shard write,
                    fsync, manifest, COMMIT marker, publish rename;
                    sync AND async flush) must leave the previous
                    committed step the loadable latest, bit-exact;
2. corruption     — bit-rot in the newest step is caught by CRC32,
                    quarantined, and restore falls back one step;
3. preemption     — SIGTERM mid-run checkpoints via save_on_signal();
                    a fresh process-equivalent resumes sample-exact and
                    matches an uninterrupted run token-for-token for
                    10 steps (params AND optimizer moments bitwise);
4. retention      — GC keeps exactly max_to_keep committed steps;
5. telemetry      — every failure path moved its counter
                    (saves_total{failed}, corrupt_total, fallback,
                    last_committed_step gauge);
6. sentinel skip  — seeded NaN gradients at a scheduled step: the
                    TrainSentinel suppresses exactly that update; final
                    params + moments bit-identical to a clean run that
                    never applied the poisoned batch;
7. sentinel rollback — a persistent NaN region: skip-batch escalates to
                    rollback to the last-known-good COMMITTED mark
                    (CheckpointManager.restore, checksum-verified) +
                    deterministic skip-forward past the quarantined
                    window; final params + moments bit-identical to a
                    clean run trained only on the healthy batches, with
                    ZERO extra XLA compiles (jit counter pinned);
8. sentinel abort — anomalies that persist through every rollback walk
                    the full escalation ladder (skip → rollback → LR
                    re-ramp + widened skip → abort) with exact counters.

Exit code 0 iff every scenario passes.

Run: JAX_PLATFORMS=cpu python tools/chaos_train.py

CI: tests/test_chaos_train.py runs every scenario as a slow-marked test
(``SCENARIOS`` below is the single source of truth).
"""
import os
import signal
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import tempfile  # noqa: E402

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
from paddle_tpu import checkpoint as ck  # noqa: E402
from paddle_tpu import faults, metrics  # noqa: E402
from paddle_tpu.io import DataLoader  # noqa: E402
from paddle_tpu.io.dataset import Dataset  # noqa: E402

SEED = int(os.environ.get("CHAOS_SEED", "0"))


class RegressionDS(Dataset):
    def __len__(self):
        return 32

    def __getitem__(self, i):
        x = np.float32([i / 32.0, 1.0 - i / 32.0, (i % 5) / 5.0])
        return x, np.float32([x @ np.float32([0.5, -0.25, 1.0])])


def build(seed=None):
    paddle.seed(SEED if seed is None else seed)
    net = nn.Linear(3, 1)
    opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                 parameters=net.parameters())
    return net, opt, nn.MSELoss()


def train_steps(net, opt, loss, loader, n, it=None):
    for _ in range(n):
        if it is None:
            it = iter(loader)
        try:
            x, y = next(it)
        except StopIteration:  # epoch rolled; loader epoch counter advanced
            it = iter(loader)
            x, y = next(it)
        l = loss(net(x), y)
        l.backward()
        opt.step()
        opt.clear_grad()
    return it


def params_of(net, opt):
    out = {f"net.{k}": np.asarray(v.numpy())
           for k, v in net.state_dict().items()}
    for k, v in opt.state_dict().items():
        if hasattr(v, "numpy"):
            out[f"opt.{k}"] = np.asarray(v.numpy())
    return out


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def _counter(name, **labels):
    fam = metrics.get_registry().get(name)
    if fam is None:
        return 0.0
    return (fam.labels(**labels) if labels else fam).value


def state_of(net, opt, loader, step):
    return ck.capture_train_state(model=net, optimizer=opt,
                                  dataloader=loader, step=step)


PHASES = [
    ("shard write", "ckpt.write", {"times": 1}),
    ("fsync", "ckpt.fsync", {"times": 1}),
    ("manifest write", "ckpt.manifest", {"times": 1}),
    ("COMMIT marker", "ckpt.commit", {"times": 1}),
    ("commit rename", "ckpt.commit", {"times": 1, "after": 1}),
]


def scenario_crash_matrix(root):
    """Fault at every phase × {sync, async flush}: the previous committed
    step must stay the latest and load bit-exact."""
    d = os.path.join(root, "matrix")
    mgr = ck.CheckpointManager(d)
    net, opt, loss = build()
    loader = DataLoader(RegressionDS(), batch_size=4, shuffle=True)
    train_steps(net, opt, loss, loader, 3)
    golden = params_of(net, opt)
    mgr.save(0, state_of(net, opt, loader, 0))
    step = 1
    for mode in ("sync", "async"):
        for label, point, sched in PHASES:
            with faults.inject(point, raise_=faults.FaultInjected,
                               seed=SEED, **sched) as spec:
                try:
                    if mode == "async":
                        mgr.save(step, state_of(net, opt, loader, step),
                                 async_save=True).wait()
                    else:
                        mgr.save(step, state_of(net, opt, loader, step))
                    _check(False, f"{mode}/{label}: save survived the fault")
                except faults.FaultInjected:
                    pass
                _check(spec.fired == 1, f"{mode}/{label}: fault never fired")
            _check(mgr.latest_step() == 0,
                   f"{mode}/{label}: latest_step "
                   f"{mgr.latest_step()} != 0 after killed save")
            res = mgr.restore_or_init()
            _check(res.restored and res.step == 0,
                   f"{mode}/{label}: restore_or_init missed step 0")
            n2, o2, _ = build(seed=SEED + 1)
            ck.restore_train_state(res.state, model=n2, optimizer=o2)
            got = params_of(n2, o2)
            for k, v in golden.items():
                _check(np.array_equal(got[k], v),
                       f"{mode}/{label}: restored leaf {k} not bit-exact")
    print(f"  [ok] crash matrix: {len(PHASES)} phases x sync+async, "
          f"step 0 never lost")


def scenario_corruption(root):
    d = os.path.join(root, "bitrot")
    mgr = ck.CheckpointManager(d)
    net, opt, loss = build()
    loader = DataLoader(RegressionDS(), batch_size=4, shuffle=True)
    mgr.save(0, state_of(net, opt, loader, 0))
    golden = params_of(net, opt)
    train_steps(net, opt, loss, loader, 2)
    mgr.save(1, state_of(net, opt, loader, 1))
    # flip one byte in a newest-step shard: size unchanged, CRC must catch
    step_dir = mgr.step_path(1)
    victim = next(os.path.join(step_dir, f) for f in os.listdir(step_dir)
                  if f.endswith(".npy"))
    with open(victim, "r+b") as fh:
        fh.seek(-1, os.SEEK_END)
        last = fh.read(1)
        fh.seek(-1, os.SEEK_END)
        fh.write(bytes([last[0] ^ 0xFF]))
    c0 = _counter("paddle_tpu_ckpt_corrupt_total")
    f0 = _counter("paddle_tpu_ckpt_restore_fallback_total")
    res = mgr.restore_or_init()
    _check(res.step == 0, f"fallback step {res.step} != 0")
    n2, o2, _ = build(seed=SEED + 1)
    ck.restore_train_state(res.state, model=n2, optimizer=o2)
    got = params_of(n2, o2)
    _check(all(np.array_equal(got[k], v) for k, v in golden.items()),
           "fallback state not bit-exact")
    _check(mgr.latest_step() == 0, "corrupt step still visible")
    _check(_counter("paddle_tpu_ckpt_corrupt_total") == c0 + 1,
           "corrupt_total did not move")
    _check(_counter("paddle_tpu_ckpt_restore_fallback_total") == f0 + 1,
           "fallback counter did not move")
    print("  [ok] corruption: CRC caught bit-rot, quarantined, fell back "
          "bit-exact")


def scenario_preemption(root):
    """SIGTERM -> save_on_signal checkpoint -> fresh resume == 10
    uninterrupted steps, token for token."""
    # uninterrupted reference
    net, opt, loss = build()
    loader = DataLoader(RegressionDS(), batch_size=4, shuffle=True)
    it = train_steps(net, opt, loss, loader, 10)
    golden = params_of(net, opt)

    # preempted run: 5 steps, SIGTERM, handler checkpoints
    d = os.path.join(root, "preempt")
    mgr = ck.CheckpointManager(d)
    net1, opt1, loss1 = build()
    loader1 = DataLoader(RegressionDS(), batch_size=4, shuffle=True)
    it1 = train_steps(net1, opt1, loss1, loader1, 5)
    scope = mgr.save_on_signal(
        lambda: (5, state_of(net1, opt1, loader1, 5)), exit_on_save=False)
    try:
        os.kill(os.getpid(), signal.SIGTERM)
    finally:
        scope.uninstall()
    _check(mgr.preempted, "preemption flag not set")
    _check(mgr.latest_step() == 5, "signal handler did not commit step 5")

    # "new process": fresh objects, wrong seed — restore must win
    net2, opt2, loss2 = build(seed=SEED + 77)
    loader2 = DataLoader(RegressionDS(), batch_size=4, shuffle=True)
    res = mgr.restore_or_init()
    _check(res.restored and res.step == 5, "resume missed step 5")
    ck.restore_train_state(res.state, model=net2, optimizer=opt2,
                           dataloader=loader2)
    train_steps(net2, opt2, loss2, loader2, 5)
    got = params_of(net2, opt2)
    bad = [k for k, v in golden.items() if not np.array_equal(got[k], v)]
    _check(not bad, f"resumed run diverged from uninterrupted: {bad}")
    print("  [ok] preemption: SIGTERM checkpointed; resume matched "
          "uninterrupted 10-step run bitwise (params + moments)")


def scenario_retention(root):
    d = os.path.join(root, "gc")
    mgr = ck.CheckpointManager(d, max_to_keep=3)
    net, opt, loss = build()
    loader = DataLoader(RegressionDS(), batch_size=4, shuffle=True)
    for s in range(7):
        train_steps(net, opt, loss, loader, 1)
        mgr.save(s, state_of(net, opt, loader, s))
    _check(mgr.all_steps() == [4, 5, 6],
           f"retention kept {mgr.all_steps()}, wanted [4, 5, 6]")
    print("  [ok] retention: GC kept last 3 of 7 committed steps")


def scenario_telemetry(root):
    d = os.path.join(root, "telemetry")
    mgr = ck.CheckpointManager(d)
    net, opt, loss = build()
    loader = DataLoader(RegressionDS(), batch_size=4, shuffle=True)
    ok0 = _counter("paddle_tpu_ckpt_saves_total", result="committed")
    fail0 = _counter("paddle_tpu_ckpt_saves_total", result="failed")
    mgr.save(0, state_of(net, opt, loader, 0))
    with faults.inject("ckpt.write", raise_=faults.FaultInjected, times=1):
        try:
            mgr.save(1, state_of(net, opt, loader, 1))
        except faults.FaultInjected:
            pass
    _check(_counter("paddle_tpu_ckpt_saves_total",
                    result="committed") == ok0 + 1, "committed did not move")
    _check(_counter("paddle_tpu_ckpt_saves_total",
                    result="failed") == fail0 + 1, "failed did not move")
    gauge = metrics.get_registry().get("paddle_tpu_ckpt_last_committed_step")
    _check(gauge is not None and gauge.value == 0,
           "last_committed_step gauge wrong")
    hist = metrics.get_registry().get("paddle_tpu_ckpt_save_seconds")
    _check(hist is not None and hist.labels(mode="sync").count >= 1,
           "save histogram empty")
    print("  [ok] telemetry: saves_total{committed,failed}, gauge, "
          "histogram all moved")


# ----------------------------------------------------------------------
# sentinel scenarios (6-8): self-healing training, ISSUE 9
# ----------------------------------------------------------------------
def _nan_grads(net):
    """Fault-point callback: poison the live gradients with NaN (the
    seeded schedule on the ``train.grads`` point decides WHEN)."""
    import jax.numpy as jnp

    from paddle_tpu.tensor import Tensor

    def poison():
        w = net.weight
        if w.grad is not None:
            w.grad = Tensor(jnp.full_like(w.grad._value, jnp.nan))
    return poison


def _guarded_run(sentinel, net, opt, loss, steps):
    """Drive a guard()-wrapped custom loop for ``steps`` guarded calls;
    returns the loader so callers can read the final stream position."""
    loader = DataLoader(RegressionDS(), batch_size=4)
    sentinel.bind(model=net, optimizer=opt, dataloader=loader)
    sentinel.note_epoch(0)
    guarded = sentinel.guard(lambda x, y: loss(net(x), y), optimizer=opt)
    it, done = iter(loader), 0
    while done < steps:
        try:
            x, y = next(it)
        except StopIteration:
            it = iter(loader)
            continue
        rep = guarded(x, y)
        if rep.rolled_back:
            it = iter(loader)  # restored position + quarantine skip
        done += 1
    return loader


def _clean_replay(loss_cls, excluded, final):
    """Reference run: same stream, to the same final position, updating
    only on batches outside ``excluded`` {(epoch, batch), ...}."""
    net, opt, loss = build()
    loader = DataLoader(RegressionDS(), batch_size=4)
    it, ep, b = iter(loader), 0, 0
    while (ep, b) != (final["epoch"], final["batch"]):
        try:
            x, y = next(it)
        except StopIteration:
            it, ep, b = iter(loader), ep + 1, 0
            continue
        cur, b = (ep, b), b + 1
        if cur in excluded:
            continue
        l = loss(net(x), y)
        l.backward()
        opt.step()
        opt.clear_grad()
    return net, opt


def _excluded_from_journal(journal):
    excluded = set()
    for e in journal:
        if e["event"] == "rollback":
            d = e["data"]
            excluded.update((d["epoch"], i) for i in
                            range(d["batch"], d["batch"] + e["skipped"]))
        elif e.get("action") == "skip":
            excluded.add((e["data"]["epoch"], e["data"]["batch"] - 1))
    return excluded


def scenario_sentinel_skip(root):
    """Seeded NaN injection at one scheduled step -> skip-batch, exact
    counters, and bit-identity to a clean run without that batch."""
    from paddle_tpu.faults import TrainSentinel

    net, opt, loss = build()
    sent = TrainSentinel(skip_limit=2, healthy_window=2, min_history=4)
    a0 = _counter("paddle_tpu_train_anomalies_total", kind="nonfinite_grad")
    s0 = _counter("paddle_tpu_train_skipped_batches_total")
    with faults.inject("train.grads", call=_nan_grads(net), seed=SEED,
                       after=5, times=1) as spec:
        loader = _guarded_run(sent, net, opt, loss, steps=14)
    _check(spec.fired == 1, "NaN fault never fired")
    _check(sent.skipped_batches == 1 and sent.rollbacks == 0,
           f"wanted exactly 1 skip, 0 rollbacks; got "
           f"{sent.skipped_batches}/{sent.rollbacks}")
    _check(_counter("paddle_tpu_train_anomalies_total",
                    kind="nonfinite_grad") == a0 + 1,
           "anomalies_total{nonfinite_grad} did not move exactly once")
    _check(_counter("paddle_tpu_train_skipped_batches_total") == s0 + 1,
           "skipped_batches_total did not move exactly once")
    excluded = _excluded_from_journal(sent.journal())
    _check(len(excluded) == 1, f"journal window wrong: {excluded}")
    n2, o2 = _clean_replay(loss, excluded, loader.state_dict())
    got, want = params_of(net, opt), params_of(n2, o2)
    bad = [k for k, v in want.items() if not np.array_equal(got[k], v)]
    _check(not bad, f"guarded run diverged from clean run: {bad}")
    print("  [ok] sentinel skip: 1 NaN batch suppressed, counters exact, "
          "params + moments bit-identical to clean run")


def scenario_sentinel_rollback(root):
    """Persistent NaN region -> rollback to the last committed mark +
    deterministic skip-forward; bit-identity to a clean run on the
    healthy batches; zero extra XLA compiles."""
    from paddle_tpu.faults import TrainSentinel

    compiles0 = _counter("paddle_tpu_jit_compiles_total")
    net, opt, loss = build()
    mgr = ck.CheckpointManager(os.path.join(root, "marks"))
    sent = TrainSentinel(skip_limit=1, healthy_window=2, mark_every=2,
                         min_history=4)
    sent.bind(manager=mgr)
    r0 = _counter("paddle_tpu_train_rollbacks_total")
    with faults.inject("train.grads", call=_nan_grads(net), seed=SEED,
                       after=5, times=3) as spec:
        loader = _guarded_run(sent, net, opt, loss, steps=18)
    _check(spec.fired == 3, f"region fault fired {spec.fired} != 3")
    _check(sent.rollbacks == 1,
           f"wanted exactly 1 rollback, got {sent.rollbacks}")
    _check(_counter("paddle_tpu_train_rollbacks_total") == r0 + 1,
           "rollbacks_total did not move exactly once")
    _check(sent.last_good_step is not None
           and sent.last_good_step in mgr.all_steps() + [sent.global_step],
           "last-known-good mark not committed")
    _check(_counter("paddle_tpu_jit_compiles_total") == compiles0,
           "guarding cost an extra XLA compile")
    excluded = _excluded_from_journal(sent.journal())
    _check(excluded, "journal recorded no quarantine window")
    n2, o2 = _clean_replay(loss, excluded, loader.state_dict())
    got, want = params_of(net, opt), params_of(n2, o2)
    bad = [k for k, v in want.items() if not np.array_equal(got[k], v)]
    _check(not bad, f"rolled-back run diverged from clean run: {bad}")
    print("  [ok] sentinel rollback: restored committed mark, skipped "
          f"{sorted(excluded)} deterministically, bit-identical to clean "
          "run, 0 extra compiles")


def scenario_sentinel_abort(root):
    """Anomalies that survive every rollback exhaust the ladder: skip ->
    rollback -> LR re-ramp + widened skip -> abort, counters exact."""
    from paddle_tpu.faults import SentinelAbort, TrainSentinel

    net, opt, loss = build()
    mgr = ck.CheckpointManager(os.path.join(root, "marks"))
    sent = TrainSentinel(skip_limit=0, lr_reramp_after=2,
                         abort_after_rollbacks=2, healthy_window=2)
    a0 = _counter("paddle_tpu_train_anomalies_total", kind="nonfinite_grad")
    r0 = _counter("paddle_tpu_train_rollbacks_total")
    rr0 = _counter("paddle_tpu_train_lr_reramps_total")
    ab0 = _counter("paddle_tpu_train_aborts_total", reason="rollback_limit")
    aborted = False
    try:
        with faults.inject("train.grads", call=_nan_grads(net), seed=SEED,
                           after=3):
            _guarded_run(sent, net, opt, loss, steps=30)
    except SentinelAbort as exc:
        aborted = True
        _check(exc.reason == "rollback_limit",
               f"abort reason {exc.reason!r} != 'rollback_limit'")
        _check(exc.journal and exc.journal[-1]["event"] == "abort",
               "abort journal missing its terminal entry")
    _check(aborted, "escalation never reached abort")
    _check(sent.rollbacks == 2, f"rollbacks {sent.rollbacks} != 2")
    _check(_counter("paddle_tpu_train_anomalies_total",
                    kind="nonfinite_grad") == a0 + 3,
           "anomaly counter not exactly 3 (rollback, rollback, abort)")
    _check(_counter("paddle_tpu_train_rollbacks_total") == r0 + 2,
           "rollbacks_total not exactly 2")
    _check(_counter("paddle_tpu_train_lr_reramps_total") == rr0 + 1,
           "lr_reramps_total not exactly 1")
    _check(_counter("paddle_tpu_train_aborts_total",
                    reason="rollback_limit") == ab0 + 1,
           "aborts_total{rollback_limit} not exactly 1")
    _check(opt.get_lr() < 0.05, "LR re-ramp never reduced the LR")
    print("  [ok] sentinel abort: 2 rollbacks + re-ramp + widened skip, "
          "then SentinelAbort with exact counters and journal")


SCENARIOS = [
    ("crash-matrix", scenario_crash_matrix),
    ("corruption", scenario_corruption),
    ("preemption", scenario_preemption),
    ("retention", scenario_retention),
    ("telemetry", scenario_telemetry),
    ("sentinel-skip", scenario_sentinel_skip),
    ("sentinel-rollback", scenario_sentinel_rollback),
    ("sentinel-abort", scenario_sentinel_abort),
]


def main():
    failures = 0
    with tempfile.TemporaryDirectory() as root:
        for name, fn in SCENARIOS:
            print(f"[chaos_train] {name} (seed={SEED})")
            faults.reset()
            try:
                fn(os.path.join(root, name))
            except Exception as exc:  # noqa: BLE001 - drill report
                failures += 1
                print(f"  [FAIL] {name}: {exc}")
            finally:
                faults.reset()
    print(f"[chaos_train] {len(SCENARIOS) - failures}/{len(SCENARIOS)} "
          f"scenarios passed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
