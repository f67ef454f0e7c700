"""Preemption-tolerant elastic training: emergency checkpoints, hang
watchdog, and re-mesh resume.

Three layers of coverage:

- **unit**: PreemptionGuard defers SIGTERM to the step boundary and is
  re-entrant; HeartbeatWriter writes atomically; DistributedSampler's
  ``consumed_samples`` is an exact, world-size-independent resume
  coordinate.
- **in-process engine**: a real training run is preempted between
  steps, emergency-saves a ``preempt-<step>`` tag, exits PREEMPT_RC,
  and a rebuilt engine (same or different DP width) resumes with a
  bit-identical (same width) / numerically identical (re-mesh) loss
  curve and zero repeated or skipped samples.
- **agent end-to-end** (the acceptance loop): a SIGTERM-preempted
  worker and a hard-hung watchdog-killed worker both auto-recover via
  ``DSElasticAgent`` with loss curves matching the uninterrupted run.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.elasticity import PREEMPT_RC, HeartbeatWriter, PreemptionGuard
from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
from deepspeed_tpu.elasticity.preemption import (read_heartbeat, read_resume_marker,
                                                 write_resume_marker)
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import make_mesh_topology
from deepspeed_tpu.runtime.dataloader import DistributedSampler
from unit.common.fault_injection import maybe_step_fault
from unit.simple_model import SimpleModel, random_dataset

HIDDEN = 32
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


# ----------------------------------------------------------------------
# unit: guard / heartbeat / sampler
# ----------------------------------------------------------------------
class TestPreemptionGuard:

    def test_sigterm_defers_to_flag(self):
        g = PreemptionGuard(grace_s=30).install()
        try:
            assert not g.preempted
            os.kill(os.getpid(), signal.SIGTERM)
            assert g.preempted          # flag set, nothing exited
            rem = g.deadline_remaining()
            assert 0 < rem <= 30
        finally:
            g.uninstall()

    def test_install_uninstall_restores_previous_handler(self):
        seen = []
        prev = signal.signal(signal.SIGTERM, lambda *a: seen.append(a))
        try:
            g = PreemptionGuard(grace_s=1).install()
            assert signal.getsignal(signal.SIGTERM) == g._handler
            g.uninstall()
            assert signal.getsignal(signal.SIGTERM) is not prev or True
            os.kill(os.getpid(), signal.SIGTERM)
            assert len(seen) == 1       # original handler back in charge
            assert not g.preempted
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_reentrant_install(self):
        for _ in range(3):
            g = PreemptionGuard(grace_s=1).install()
            g.uninstall()
        assert signal.getsignal(signal.SIGTERM) in (signal.SIG_DFL, signal.default_int_handler) \
            or callable(signal.getsignal(signal.SIGTERM))

    def test_deadline_none_until_requested(self):
        g = PreemptionGuard(grace_s=5)
        assert g.deadline_remaining() is None
        g.request()
        assert g.deadline_remaining() is not None
        g.reset()
        assert g.deadline_remaining() is None


class TestHeartbeat:

    def test_noop_when_unset(self, monkeypatch):
        monkeypatch.delenv("DS_HEARTBEAT_FILE", raising=False)
        hb = HeartbeatWriter()
        assert not hb.enabled
        hb.beat(1)  # must not raise or create anything

    def test_beat_atomic_payload(self, tmpdir):
        path = os.path.join(str(tmpdir), "hb.json")
        hb = HeartbeatWriter(path=path)
        hb.beat(7)
        payload = read_heartbeat(path)
        assert payload["step"] == 7 and payload["time"] > 0
        hb.beat(7)  # same step: no rewrite needed, still intact
        assert read_heartbeat(path)["step"] == 7
        hb.beat(8)
        assert read_heartbeat(path)["step"] == 8
        assert not os.path.exists(path + f".tmp.{os.getpid()}")

    def test_torn_read_returns_none(self, tmpdir):
        path = os.path.join(str(tmpdir), "hb.json")
        with open(path, "w") as fd:
            fd.write('{"step": 3,')
        assert read_heartbeat(path) is None
        assert read_heartbeat(os.path.join(str(tmpdir), "missing")) is None


class TestSamplerResume:
    """consumed_samples is a world-size-independent resume coordinate:
    the global order is a function of the seed alone."""

    def _global_stream(self, n, replicas, seed=3, epochs=2):
        """Consume the full stream at width ``replicas``, interleaving
        ranks the way simultaneous replicas would."""
        samplers = [DistributedSampler(n, replicas, r, seed=seed) for r in range(replicas)]
        out = []
        for _ in range(epochs):
            iters = [iter(s) for s in samplers]
            for _ in range(samplers[0].total_size // replicas):
                chunk = [next(it) for it in iters]
                out.extend(chunk)
                for s in samplers:
                    s.advance(replicas)
        return out

    @pytest.mark.parametrize("n,replicas", [(16, 2), (16, 4), (24, 3)])
    def test_epoch_coverage_exact(self, n, replicas):
        stream = self._global_stream(n, replicas, epochs=1)
        assert sorted(stream) == list(range(n))  # each sample exactly once

    @pytest.mark.parametrize("w_from,w_to", [(2, 1), (1, 2), (4, 2)])
    def test_resume_across_width_change_no_repeat_no_skip(self, w_from, w_to):
        n, seed = 16, 11
        reference = self._global_stream(n, 1, seed=seed, epochs=2)

        # consume 12 samples at width w_from
        consumed = 12
        first = []
        samplers = [DistributedSampler(n, w_from, r, seed=seed) for r in range(w_from)]
        iters = [iter(s) for s in samplers]
        for _ in range(consumed // w_from):
            first.extend(next(it) for it in iters)
            for s in samplers:
                s.advance(w_from)
        sd = samplers[0].state_dict()
        assert sd["consumed_samples"] == consumed

        # resume at width w_to, consume the rest of both epochs
        resumed = [DistributedSampler(n, w_to, r, seed=seed) for r in range(w_to)]
        for r_i, s in enumerate(resumed):
            s.load_state_dict(sd, num_replicas=w_to, rank=r_i)
        second = []
        remaining = 2 * n - consumed
        while remaining > 0:
            iters = [iter(s) for s in resumed]
            in_epoch = (resumed[0].total_size - resumed[0].consumed_samples
                        % resumed[0].total_size) % resumed[0].total_size or resumed[0].total_size
            take = min(remaining, in_epoch) // w_to
            for _ in range(take):
                second.extend(next(it) for it in iters)
                for s in resumed:
                    s.advance(w_to)
            remaining -= take * w_to
        assert first + second == reference  # zero repeats, zero skips

    def test_set_epoch_resets_consumption(self):
        s = DistributedSampler(8, 1, 0, seed=0)
        s.advance(8)
        s.set_epoch(1)
        assert s.consumed_samples == 0
        # epoch 1 permutation from the start
        assert list(iter(s)) == list(np.random.RandomState(1).permutation(8))


# ----------------------------------------------------------------------
# in-process engine: emergency checkpoint + re-mesh resume
# ----------------------------------------------------------------------
class _RecordingDataset:
    """list-backed dataset recording every index served."""

    def __init__(self, data):
        self.data = data
        self.served = []

    def __len__(self):
        return len(self.data)

    def __getitem__(self, idx):
        self.served.append(int(idx))
        return self.data[idx]


def _make_engine(ckpt_dir, dp=None, nebula=True, record=False):
    """Fresh engine over a SimpleModel; ``dp`` selects the mesh's data
    width (subset of the 8 virtual devices); LR schedule included so
    resume continuity is observable."""
    groups.destroy_mesh()
    mesh = None
    if dp is not None:
        mesh = make_mesh_topology(data=dp, devices=jax.devices()[:dp])
    # One process drives the whole mesh: the loader serves the full
    # 8-sample step batch regardless of width, so the sample stream and
    # per-step math are width-invariant; the config's dp replica count
    # (the explicit mesh's data axis) only scales train_batch_size.
    config = {
        "train_batch_size": 8 * (dp if dp is not None else 1),
        "train_micro_batch_size_per_gpu": 8,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 1e-2,
                                 "warmup_num_steps": 4}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 10**9,
    }
    if dp is None:
        config["mesh"] = {"data_parallel_size": 8}
    if nebula:
        config["nebula"] = {"enabled": True, "persistent_storage_path": str(ckpt_dir),
                            "persistent_time_interval": 0}
    dataset = random_dataset(64, HIDDEN, seed=5)
    if record:
        dataset = _RecordingDataset(dataset)
    engine, _, _, _ = deepspeed_tpu.initialize(model=SimpleModel(hidden_dim=HIDDEN, nlayers=2),
                                               config=config, training_data=dataset, mesh=mesh)
    return engine, dataset


def _train(engine, steps, losses):
    it = iter(engine.training_dataloader)
    for _ in range(steps):
        losses.append(float(engine.train_batch(data_iter=it)))


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(x), tree)


class TestEngineEmergencyCheckpoint:

    TOTAL = 6
    PREEMPT_AFTER = 3  # SIGTERM lands after this many steps

    def _reference(self, ckpt_dir, dp=None):
        engine, dataset = _make_engine(ckpt_dir, dp=dp, nebula=False, record=True)
        losses = []
        try:
            _train(engine, self.TOTAL, losses)
            return {"losses": losses, "params": _host(engine.params),
                    "opt": _host(engine.opt_state), "lr": engine.get_lr()[0],
                    "steps": engine.global_steps, "samples": engine.global_samples,
                    "served": list(dataset.served),
                    "consumed": engine.training_dataloader.data_sampler.consumed_samples}
        finally:
            engine.destroy()

    def _preempted_run(self, ckpt_dir, monkeypatch, dp=None):
        """Train PREEMPT_AFTER steps, SIGTERM, finish one more step, and
        verify the emergency exit contract. Returns the pre-exit losses."""
        monkeypatch.setenv("DS_ELASTIC_ENABLED", "1")
        engine, dataset = _make_engine(ckpt_dir, dp=dp, record=True)
        losses = []
        try:
            it = iter(engine.training_dataloader)
            for _ in range(self.PREEMPT_AFTER):
                losses.append(float(engine.train_batch(data_iter=it)))
            os.kill(os.getpid(), signal.SIGTERM)
            assert engine._preemption_guard.preempted
            with pytest.raises(SystemExit) as ei:
                engine.train_batch(data_iter=it)  # finishes the step, then exits
            assert ei.value.code == PREEMPT_RC
            losses.append(float(engine.losses))  # the in-flight step completed
            step = self.PREEMPT_AFTER + 1
            assert engine.global_steps == step
            from deepspeed_tpu.nebula.service import resolve_load_tag, validate_tag
            assert validate_tag(str(ckpt_dir), f"preempt-{step}")
            assert resolve_load_tag(str(ckpt_dir)) == f"preempt-{step}"
            marker = read_resume_marker(str(ckpt_dir))
            assert marker and marker["tag"] == f"preempt-{step}" and marker["step"] == step
            return losses, list(dataset.served)
        finally:
            engine.destroy()

    def _resume_run(self, ckpt_dir, monkeypatch, dp=None, steps=None):
        monkeypatch.setenv("DS_ELASTIC_ENABLED", "1")
        monkeypatch.setenv("DS_ELASTIC_RESTART_COUNT", "1")
        engine, dataset = _make_engine(ckpt_dir, dp=dp, record=True)
        losses = []
        try:
            # materialize device state from one throwaway batch, then load
            engine.train_batch(data_iter=iter(engine.training_dataloader))
            served_before_load = len(dataset.served)
            load_dir, _ = engine.load_checkpoint()
            assert load_dir is not None
            assert read_resume_marker(str(ckpt_dir)) is None  # marker consumed
            remaining = (steps if steps is not None
                         else self.TOTAL - engine.global_steps)
            _train(engine, remaining, losses)
            return {"losses": losses, "params": _host(engine.params),
                    "opt": _host(engine.opt_state), "lr": engine.get_lr()[0],
                    "steps": engine.global_steps, "samples": engine.global_samples,
                    "served": list(dataset.served)[served_before_load:],
                    "consumed": engine.training_dataloader.data_sampler.consumed_samples}
        finally:
            engine.destroy()

    def test_preempt_resume_same_width_bit_identical(self, tmpdir, monkeypatch):
        ref = self._reference(os.path.join(str(tmpdir), "ref"))
        ckpt = os.path.join(str(tmpdir), "ckpt")
        pre_losses, pre_served = self._preempted_run(ckpt, monkeypatch)
        res = self._resume_run(ckpt, monkeypatch)

        # loss curve bit-identical to the uninterrupted run
        assert pre_losses == ref["losses"][:len(pre_losses)]
        assert res["losses"] == ref["losses"][len(pre_losses):]
        assert res["steps"] == ref["steps"]
        assert res["samples"] == ref["samples"]
        assert res["lr"] == ref["lr"]
        assert res["consumed"] == ref["consumed"]
        # zero repeated, zero skipped samples across the preemption
        assert pre_served + res["served"] == ref["served"]
        # final state exact
        for a, b in zip(jax.tree.leaves(ref["params"]), jax.tree.leaves(res["params"])):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(jax.tree.leaves(ref["opt"]), jax.tree.leaves(res["opt"])):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("dp_from,dp_to", [(2, 1), (1, 2)])
    def test_preempt_resume_across_dp_widths(self, tmpdir, monkeypatch, dp_from, dp_to):
        """DP width changes between preempt and resume: the sharded
        engine reshards, LR/step/consumed-sample continuity is exact,
        and the state matches the uninterrupted reference run."""
        ref = self._reference(os.path.join(str(tmpdir), "ref"), dp=dp_from)
        ckpt = os.path.join(str(tmpdir), "ckpt")
        pre_losses, pre_served = self._preempted_run(ckpt, monkeypatch, dp=dp_from)
        res = self._resume_run(ckpt, monkeypatch, dp=dp_to)

        assert pre_losses == ref["losses"][:len(pre_losses)]
        assert res["steps"] == ref["steps"]
        assert res["lr"] == ref["lr"]
        assert res["consumed"] == ref["consumed"]
        assert pre_served + res["served"] == ref["served"]
        np.testing.assert_allclose(res["losses"], ref["losses"][len(pre_losses):],
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(ref["params"]), jax.tree.leaves(res["params"])):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(ref["opt"]), jax.tree.leaves(res["opt"])):
            np.testing.assert_allclose(np.asarray(a, np.float64), np.asarray(b, np.float64),
                                       rtol=1e-5, atol=1e-6)

    def test_no_guard_without_elastic_env(self, tmpdir, monkeypatch):
        monkeypatch.delenv("DS_ELASTIC_ENABLED", raising=False)
        engine, _ = _make_engine(os.path.join(str(tmpdir), "c"))
        try:
            assert engine._preemption_guard is None
        finally:
            engine.destroy()

    def test_emergency_ckpt_kill_switch(self, tmpdir, monkeypatch):
        monkeypatch.setenv("DS_ELASTIC_ENABLED", "1")
        monkeypatch.setenv("DS_EMERGENCY_CKPT", "0")
        engine, _ = _make_engine(os.path.join(str(tmpdir), "c"))
        try:
            assert engine._preemption_guard is None
        finally:
            engine.destroy()


# ----------------------------------------------------------------------
# agent: watchdog + preemption forwarding (no JAX in these workers)
# ----------------------------------------------------------------------
class TestAgentWatchdog:

    def _beating_script(self, d, beats, then):
        """Worker that heartbeats ``beats`` steps then ``then`` ∈
        {"hang", "exit"}; relaunches always exit clean."""
        marker = os.path.join(d, "attempts")
        script = os.path.join(d, "w.py")
        with open(script, "w") as f:
            f.write(f"""
import json, os, sys, time
sys.path.insert(0, {REPO_ROOT!r})
from deepspeed_tpu.elasticity.preemption import HeartbeatWriter
with open({marker!r}, "a") as m:
    m.write(os.environ.get("DS_ELASTIC_RESTART_COUNT", "?") + "\\n")
restarted = int(os.environ.get("DS_ELASTIC_RESTART_COUNT", "0")) > 0
hb = HeartbeatWriter()
assert hb.enabled, "agent must export DS_HEARTBEAT_FILE when the watchdog is armed"
for step in range({beats}):
    hb.beat(step)
    time.sleep(0.05)
if not restarted and {then!r} == "hang":
    while True:
        time.sleep(3600)
sys.exit(0)
""")
        return script, marker

    def test_watchdog_kills_hung_worker_and_relaunches(self):
        with tempfile.TemporaryDirectory() as d:
            script, marker = self._beating_script(d, beats=3, then="hang")
            agent = DSElasticAgent([sys.executable, script], max_restarts=2,
                                   monitor_interval=0.1, watchdog_timeout=1.0,
                                   preempt_grace=0.5)
            assert agent.run() == 0
            assert agent.hang_count == 1
            assert open(marker).read().split() == ["0", "1"]

    def test_watchdog_not_armed_before_first_beat(self):
        """Startup/compile time is not a hang: a worker that takes longer
        than the watchdog timeout before its FIRST beat must not be shot."""
        with tempfile.TemporaryDirectory() as d:
            script = os.path.join(d, "w.py")
            with open(script, "w") as f:
                f.write("import time\ntime.sleep(1.2)\n")  # > watchdog, no beats
            agent = DSElasticAgent([sys.executable, script], max_restarts=0,
                                   monitor_interval=0.1, watchdog_timeout=0.5,
                                   preempt_grace=0.5)
            assert agent.run() == 0
            assert agent.hang_count == 0

    def test_watchdog_counts_against_failure_window(self):
        with tempfile.TemporaryDirectory() as d:
            script = os.path.join(d, "w.py")
            with open(script, "w") as f:
                f.write(f"""
import os, sys, time
sys.path.insert(0, {REPO_ROOT!r})
from deepspeed_tpu.elasticity.preemption import HeartbeatWriter
hb = HeartbeatWriter(); hb.beat(1)
while True:
    time.sleep(3600)
""")
            agent = DSElasticAgent([sys.executable, script], max_restarts=1,
                                   monitor_interval=0.1, watchdog_timeout=0.6,
                                   preempt_grace=0.3)
            rc = agent.run()
            assert rc != 0                      # hung twice: budget exhausted
            assert agent.hang_count == 2

    def test_preempt_rc_relaunches_outside_failure_budget(self):
        """A fleet preempted repeatedly is not a crash loop: PREEMPT_RC
        relaunches even with max_restarts=0."""
        with tempfile.TemporaryDirectory() as d:
            marker = os.path.join(d, "attempts")
            script = os.path.join(d, "w.py")
            with open(script, "w") as f:
                f.write(f"""
import os, sys
sys.path.insert(0, {REPO_ROOT!r})
from deepspeed_tpu.elasticity.preemption import PREEMPT_RC
with open({marker!r}, "a") as m:
    m.write(os.environ.get("DS_ELASTIC_RESTART_COUNT", "?") + "\\n")
n = sum(1 for _ in open({marker!r}))
sys.exit(PREEMPT_RC if n <= 2 else 0)
""")
            agent = DSElasticAgent([sys.executable, script], max_restarts=0,
                                   monitor_interval=0.05)
            assert agent.run() == 0
            assert agent.preempt_count == 2
            assert open(marker).read().split() == ["0", "1", "2"]

    def test_sigterm_forwarded_with_grace(self):
        """Agent shutdown forwards SIGTERM and honors the grace budget:
        a worker that traps SIGTERM, finishes its 'step', and exits
        PREEMPT_RC counts as a clean shutdown."""
        with tempfile.TemporaryDirectory() as d:
            done, ready = os.path.join(d, "done"), os.path.join(d, "ready")
            script = os.path.join(d, "w.py")
            with open(script, "w") as f:
                f.write(f"""
import os, signal, sys, time
sys.path.insert(0, {REPO_ROOT!r})
from deepspeed_tpu.elasticity.preemption import PREEMPT_RC, PreemptionGuard
g = PreemptionGuard(grace_s=10).install()
open({ready!r}, "w").write("guarded")
while not g.preempted:
    time.sleep(0.05)
time.sleep(0.3)  # "finish the in-flight step"
open({done!r}, "w").write("saved")
sys.exit(PREEMPT_RC)
""")
            agent = DSElasticAgent([sys.executable, script], max_restarts=1,
                                   monitor_interval=0.1, preempt_grace=10.0)
            result = {}
            t = threading.Thread(target=lambda: result.update(rc=agent.run()))
            t.start()
            # the guard is installed when the worker says so, not after a second: importing
            # ``deepspeed_tpu`` takes longer than that on a loaded machine, and a SIGTERM
            # before the guard is the default handler's
            waited = time.monotonic()
            while not os.path.exists(ready):
                assert time.monotonic() - waited < 30, "the worker never installed its guard"
                time.sleep(0.05)
            agent.shutdown()
            t.join(timeout=30)
            assert not t.is_alive()
            assert result["rc"] == 0            # preempt exit == clean shutdown
            assert open(done).read() == "saved"  # worker got its grace window

    def test_run_restores_signal_handlers(self):
        """Satellite: run() must save/restore SIGINT/SIGTERM handlers so
        the agent is re-entrant in tests."""
        prev_term = signal.getsignal(signal.SIGTERM)
        prev_int = signal.getsignal(signal.SIGINT)
        with tempfile.TemporaryDirectory() as d:
            script = os.path.join(d, "w.py")
            with open(script, "w") as f:
                f.write("raise SystemExit(0)\n")
            agent = DSElasticAgent([sys.executable, script], max_restarts=0,
                                   monitor_interval=0.05)
            assert agent.run() == 0
        assert signal.getsignal(signal.SIGTERM) == prev_term
        assert signal.getsignal(signal.SIGINT) == prev_int


# ----------------------------------------------------------------------
# acceptance: agent-supervised training, faulted vs uninterrupted
# ----------------------------------------------------------------------
_TRAIN_WORKER = """
import json, os, signal, sys
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import deepspeed_tpu
from deepspeed_tpu.models import build_llama
from unit.common.fault_injection import maybe_step_fault

CKPT = os.environ["TEST_CKPT"]
LOSSES = os.environ["TEST_LOSSES"]
FAULT = os.environ.get("TEST_FAULT") or None
TOTAL, AT = 4, 2
engine, _, _, _ = deepspeed_tpu.initialize(model=build_llama("debug"), config={
    "train_batch_size": 8, "train_micro_batch_size_per_gpu": 8,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 1}, "steps_per_print": 10**9,
    "nebula": {"enabled": True, "persistent_storage_path": CKPT,
               "persistent_time_interval": 0}})
ids = np.random.RandomState(0).randint(0, 256, size=(8, 16)).astype(np.int32)
batch = (jnp.asarray(ids), jnp.asarray(ids))
restarted = int(os.environ.get("DS_ELASTIC_RESTART_COUNT", "0")) > 0
if restarted:
    engine.train_batch(batch=batch)   # materialize shardings
    engine.load_checkpoint()
try:
    while engine.global_steps < TOTAL:
        loss = float(engine.train_batch(batch=batch))
        with open(LOSSES, "a") as f:
            f.write(f"{engine.global_steps} {loss!r}\\n")
        engine.save_checkpoint(async_save=False)
        maybe_step_fault(FAULT, engine.global_steps, AT, armed=not restarted)
except SystemExit:
    # preempted mid-loop: the in-flight step completed and was
    # emergency-checkpointed before the exit — record its loss too
    if engine.losses is not None:
        with open(LOSSES, "a") as f:
            f.write(f"{engine.global_steps} {float(engine.losses)!r}\\n")
    raise
engine.destroy()
"""


def _read_curve(path):
    out = []
    for line in open(path):
        step, loss = line.split()
        out.append((int(step), float(loss)))
    return out


@pytest.fixture(scope="module")
def reference_curve(tmp_path_factory):
    """Uninterrupted agent-free run of the same worker."""
    d = tmp_path_factory.mktemp("ref")
    losses = str(d / "losses.txt")
    env = {**os.environ, "PYTHONPATH": f"{REPO_ROOT}:{REPO_ROOT}/tests",
           "TEST_CKPT": str(d / "ckpt"), "TEST_LOSSES": losses, "TEST_FAULT": ""}
    script = str(d / "train.py")
    with open(script, "w") as f:
        f.write(_TRAIN_WORKER)
    subprocess.run([sys.executable, script], env=env, cwd=REPO_ROOT,
                   timeout=300, check=True)
    return _read_curve(losses)


class TestAcceptance:
    """ISSUE 7 acceptance: SIGTERM-preempted and watchdog-killed training
    runs auto-recover via the agent with bit-identical loss curves."""

    def _run_agent(self, d, fault, **agent_kw):
        losses = os.path.join(d, "losses.txt")
        script = os.path.join(d, "train.py")
        with open(script, "w") as f:
            f.write(_TRAIN_WORKER)
        env_base = {**os.environ, "PYTHONPATH": f"{REPO_ROOT}:{REPO_ROOT}/tests",
                    "TEST_CKPT": os.path.join(d, "ckpt"), "TEST_LOSSES": losses,
                    "TEST_FAULT": fault}
        agent = DSElasticAgent([sys.executable, script], env_fn=lambda: dict(env_base),
                               max_restarts=2, monitor_interval=0.2, **agent_kw)
        rc = agent.run()
        return rc, agent, _read_curve(losses)

    def _assert_curve_matches(self, curve, reference):
        ref = dict(reference)
        assert curve, "worker never trained"
        for step, loss in curve:
            assert loss == ref[step], (
                f"loss at step {step} diverged after recovery: {loss!r} != {ref[step]!r}")
        assert max(s for s, _ in curve) == max(ref)  # ran to completion
        # zero steps lost: every step from the faulted run's last
        # checkpoint to completion is present
        seen = {s for s, _ in curve}
        assert seen == set(ref), f"missing steps {set(ref) - seen}"

    def test_preempted_run_recovers_bit_identical(self, reference_curve):
        with tempfile.TemporaryDirectory() as d:
            rc, agent, curve = self._run_agent(d, "preempt", preempt_grace=60.0)
            assert rc == 0
            assert agent.preempt_count == 1
            assert agent.restart_count == 1
            self._assert_curve_matches(curve, reference_curve)

    def test_hung_run_watchdog_recovers_bit_identical(self, reference_curve):
        with tempfile.TemporaryDirectory() as d:
            rc, agent, curve = self._run_agent(d, "hang", watchdog_timeout=5.0,
                                               preempt_grace=1.0)
            assert rc == 0
            assert agent.hang_count == 1
            self._assert_curve_matches(curve, reference_curve)
