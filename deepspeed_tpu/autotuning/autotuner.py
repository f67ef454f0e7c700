"""Autotuner: search ZeRO stage × micro-batch for best throughput.

Capability match for the reference's ``deepspeed/autotuning/autotuner.py``
(``Autotuner`` at autotuner.py:42: builds an experiment grid over
zero-stage/micro-batch tuning spaces, launches each config, ranks by a
metric). Two execution modes:

- **in-process** (``tune()``): each candidate config builds an engine on
  the live mesh, times a few fused ``train_batch`` steps (first step
  discarded: XLA compile), and the grid is pruned stage-first exactly
  like the reference's ``tune_space`` fast mode.
- **distributed** (``tune_distributed()``): the grid is materialized as
  a reference-style results tree (one dir per experiment with
  ``exp.json`` / ``exp_result.json`` / logs) and the experiments run as
  SUBPROCESSES scheduled over a hostfile by
  ``autotuning/scheduler.ResourceManager`` (ssh to remote hosts, the
  local interpreter for localhost) — the reference's
  ``scheduler.py:32`` experiment scheduler.

Results and the winning ds_config are written as JSON next to the
experiment dirs either way.
"""

import copy
import json
import os
import time

import numpy as np

from deepspeed_tpu.utils.logging import logger

DEFAULT_MICRO_BATCHES = (1, 2, 4, 8, 16, 32)
DEFAULT_ZERO_STAGES = (0, 1, 2, 3)

AUTOTUNING = "autotuning"
AUTOTUNING_ENABLED_DEFAULT = False


class Autotuner:
    """In-process experiment grid.

    Args:
        model_fn: zero-arg callable returning a FRESH model (a flax
            module); rebuilt per experiment.
        base_config: ds_config dict; ``train_micro_batch_size_per_gpu``
            and ``zero_optimization.stage`` are overridden per candidate.
        batch_fn: ``batch_fn(micro_batch_size) -> (args...)`` producing
            one micro-batch of synthetic data.
        micro_batches / zero_stages: candidate lists.
        steps: timed steps per experiment (after one compile step).
    """

    def __init__(self, model_fn, base_config, batch_fn, micro_batches=None,
                 zero_stages=None, steps=3, mesh=None, results_dir=None,
                 metric="throughput", autotuning_config=None,
                 model_spec=None, batch_spec=None,
                 gas_candidates=None, offload_candidates=None,
                 memory_budget_bytes=None, world_size=None):
        self.model_fn = model_fn
        self.base_config = base_config
        self.batch_fn = batch_fn
        # extra search dims (reference tuning space includes gradient
        # accumulation and offload configs): defaults keep the classic
        # stage x micro-batch grid. offload=None means "leave base_config
        # alone"; searching [False, True] explicitly strips/adds it.
        self.gas_candidates = list(gas_candidates) if gas_candidates else [None]
        self.offload_candidates = (list(offload_candidates)
                                   if offload_candidates else [None])
        # HBM budget for the pre-prune memory model (reference
        # autotuner.py:663 profiles model info to prune the space;
        # mem_model.py estimates from eval_shape + jaxpr walk instead)
        self.memory_budget_bytes = memory_budget_bytes
        # Devices each experiment runs on, for the memory model. None is
        # resolved where the experiments run: tune() asks JAX in this
        # process; tune_distributed() takes its slots_per_exp, because a
        # parent that touches JAX holds the chip its workers need.
        self.world_size = None if world_size is None else int(world_size)
        # JSON-able specs for the distributed mode's out-of-process
        # workers (exp_runner.py schema)
        self.model_spec = model_spec
        self.batch_spec = batch_spec
        self.micro_batches = list(micro_batches or DEFAULT_MICRO_BATCHES)
        self.zero_stages = list(zero_stages or DEFAULT_ZERO_STAGES)
        self.steps = steps
        self.mesh = mesh
        self.metric = metric
        self.results_dir = results_dir
        if autotuning_config is None and isinstance(base_config.get("autotuning"), dict):
            from deepspeed_tpu.autotuning.config import get_autotuning_config
            autotuning_config = get_autotuning_config(base_config)
        if autotuning_config is not None:
            lo = autotuning_config.min_train_micro_batch_size_per_gpu
            hi = autotuning_config.max_train_micro_batch_size_per_gpu
            self.micro_batches = [m for m in self.micro_batches
                                  if m >= lo and (hi is None or m <= hi)]
            # config overrides only the fields the user actually set —
            # an explicit constructor argument wins otherwise
            set_fields = getattr(autotuning_config, "model_fields_set",
                                 getattr(autotuning_config, "__fields_set__", set()))
            if "metric" in set_fields:
                self.metric = autotuning_config.metric
            if "results_dir" in set_fields and results_dir is None:
                self.results_dir = autotuning_config.results_dir
        self.results = []
        self.best = None

    # ------------------------------------------------------------------
    def _experiment_config(self, stage, mbs, gas=None, offload=None):
        cfg = copy.deepcopy(self.base_config)
        cfg["train_micro_batch_size_per_gpu"] = mbs
        if gas is not None:
            cfg["gradient_accumulation_steps"] = gas
        else:
            cfg.setdefault("gradient_accumulation_steps", 1)
        zc = cfg.setdefault("zero_optimization", {})
        zc["stage"] = stage
        if offload is True:
            zc["offload_optimizer"] = {"device": "cpu"}
        elif offload is False:
            # the non-offload lane must really run non-offloaded even when
            # base_config carries an offload_optimizer section
            zc.pop("offload_optimizer", None)
        # the config triangulation derives train_batch_size from
        # micro×gas×world — setting it here would double-specify and can
        # silently inflate gradient accumulation
        cfg.pop("train_batch_size", None)
        return cfg

    def estimate_memory(self, stage, mbs, gas=None, offload=None):
        """Per-device HBM estimate for a candidate (mem_model.py). The
        forward trace is cached per micro-batch size — sweeping
        stage/gas/offload costs integer arithmetic only."""
        from deepspeed_tpu.autotuning.mem_model import estimate_experiment_memory
        if not hasattr(self, "_mem_trace_cache"):
            self._mem_trace_cache = {}
        if self.world_size is None:
            import jax
            self.world_size = len(jax.devices())
        return estimate_experiment_memory(
            self.model_fn, self.batch_fn,
            self._experiment_config(stage, mbs, gas, offload), mbs,
            world_size=self.world_size, _trace_cache=self._mem_trace_cache)

    def _prune_by_memory(self, stage, mbs, gas, offload):
        """→ record dict if the estimator rejects the candidate (recorded
        WITHOUT running it — no compile, no OOM), else None."""
        if self.memory_budget_bytes is None:
            return None
        try:
            est = self.estimate_memory(stage, mbs, gas, offload)
        except Exception as e:  # estimator must never block tuning
            logger.warning(f"autotune: memory estimate failed ({e}); running anyway")
            return None
        if est["total_bytes"] <= self.memory_budget_bytes:
            return None
        rec = {"zero_stage": stage, "micro_batch_size": mbs,
               "gas": gas, "offload": offload,
               "metric": self.metric, "value": None,
               "error": (f"estimated OOM: {est['total_bytes'] / 1e9:.2f} GB "
                         f"> budget {self.memory_budget_bytes / 1e9:.2f} GB "
                         f"(pruned without running)"),
               "memory_estimate": est}
        self.results.append(rec)
        logger.info(f"autotune: pruned stage={stage} mbs={mbs} gas={gas} "
                    f"offload={offload}: {rec['error']}")
        return rec

    @staticmethod
    def _features(cand):
        """Cost-model features for one (stage, mbs, gas, offload)
        candidate (reference tuner/cost_model.py learns over the same
        config dims)."""
        stage, mbs, gas, offload = cand
        return np.array([1.0, np.log(float(mbs)), float(stage),
                         float(gas or 1), 1.0 if offload else 0.0])

    def run_experiment(self, stage, mbs, gas=None, offload=None):
        """One candidate: build a fresh engine, time train_batch."""
        import deepspeed_tpu
        from deepspeed_tpu.parallel import groups

        record = {"zero_stage": stage, "micro_batch_size": mbs,
                  "gas": gas, "offload": offload,
                  "metric": self.metric, "value": None, "error": None}
        cfg = self._experiment_config(stage, mbs, gas, offload)
        try:
            if self.mesh is None:
                groups.destroy_mesh()
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=self.model_fn(), config=cfg, mesh=self.mesh)
            gas = engine.gradient_accumulation_steps()
            batch = self.batch_fn(mbs)
            stacked = tuple(np.stack([np.asarray(a)] * gas) for a in batch)
            engine.train_batch(batch=stacked)  # compile step
            t0 = time.perf_counter()
            for _ in range(self.steps):
                engine.train_batch(batch=stacked)
            dt = (time.perf_counter() - t0) / self.steps
            # throughput over the samples actually fed (mbs * gas), not the
            # config's train_batch_size (whose world factor may differ)
            record["value"] = (mbs * gas) / dt  # samples/sec
            record["step_time_s"] = dt
        except Exception as e:  # OOM / compile failure → prune candidate
            record["error"] = f"{type(e).__name__}: {e}"
            logger.warning(f"autotune: stage={stage} mbs={mbs} failed: {record['error'][:200]}")
        finally:
            if self.mesh is None:
                groups.destroy_mesh()
        self.results.append(record)
        return record

    def tune(self, strategy="hillclimb", num_trials=None, seed=0):
        """Search the stage (x offload x gas) x micro-batch space.

        ``strategy`` mirrors the reference ``tuner/`` package:

        - ``"hillclimb"`` (default; the reference's fast mode): within a
          lane, stop growing the micro-batch after the first failure or
          regression.
        - ``"grid"`` (GridSearchTuner): every candidate runs.
        - ``"random"`` (RandomTuner): ``num_trials`` candidates sampled
          without replacement from the full product.
        - ``"model_based"`` (ModelBasedTuner + cost_model, reference
          ``tuner/model_based_tuner.py``): seed with a few random
          evaluations, then repeatedly fit a least-squares cost model
          (log-throughput over the candidate's numeric features) on every
          result so far and run the unevaluated candidate the model ranks
          best, up to ``num_trials`` total experiments.

        Candidates the memory model rejects are recorded as pruned
        without ever running — no compile, no OOM (crash-prune remains
        the backstop)."""
        import random as _random
        space = [(stage, offload, gas)
                 for stage in self.zero_stages
                 for offload in self.offload_candidates
                 for gas in self.gas_candidates]
        product = [(s, m, g, o) for (s, o, g) in space
                   for m in sorted(self.micro_batches)]
        if strategy in ("grid", "random"):
            candidates = product
            if strategy == "random":
                k = min(num_trials or len(candidates), len(candidates))
                candidates = _random.Random(seed).sample(candidates, k)
            for stage, mbs, gas, offload in candidates:
                if self._prune_by_memory(stage, mbs, gas, offload) is None:
                    self.run_experiment(stage, mbs, gas, offload)
        elif strategy == "hillclimb":
            for stage, offload, gas in space:
                prev = None
                for mbs in sorted(self.micro_batches):
                    pruned = self._prune_by_memory(stage, mbs, gas, offload)
                    if pruned is not None:
                        break  # larger mbs only estimates bigger
                    rec = self.run_experiment(stage, mbs, gas, offload)
                    if rec["error"] is not None:
                        break
                    if prev is not None and rec["value"] is not None and \
                            rec["value"] < prev * 0.98:
                        break
                    prev = rec["value"]
        elif strategy == "model_based":
            candidates = [c for c in product
                          if self._prune_by_memory(*c) is None]
            budget = min(num_trials or max(3, len(candidates) // 2), len(candidates))
            rng = _random.Random(seed)
            seeds = rng.sample(candidates, min(3, budget))
            evaluated = {}
            for c in seeds:
                evaluated[c] = self.run_experiment(*c)
            while len(evaluated) < budget:
                remaining = [c for c in candidates if c not in evaluated]
                if not remaining:
                    break
                scored = [(c, r["value"]) for c, r in evaluated.items()
                          if r["value"] is not None]
                if len(scored) >= 2:
                    X = np.array([self._features(c) for c, _ in scored])
                    y = np.log([v for _, v in scored])
                    coef, *_ = np.linalg.lstsq(X, y, rcond=None)
                    remaining.sort(key=lambda c: -float(self._features(c) @ coef))
                # else: no usable signal yet — fall through in listed order
                evaluated[remaining[0]] = self.run_experiment(*remaining[0])
        else:
            raise ValueError(
                f"unknown strategy {strategy!r}: hillclimb | grid | random | model_based")
        ok = [r for r in self.results if r["value"] is not None]
        if not ok:
            raise RuntimeError("autotuning: every experiment failed; see results")
        self.best = max(ok, key=lambda r: r["value"])
        if self.results_dir:
            self.write_results()
        return self._experiment_config(self.best["zero_stage"], self.best["micro_batch_size"],
                                       self.best.get("gas"), self.best.get("offload"))

    def tune_distributed(self, hosts=None, hostfile=None, env=None,
                         slots_per_exp=1, timeout=None):
        """Run the stage x micro-batch (x gas x offload) grid as
        scheduled subprocesses over ``hosts`` ({hostname: slots}) or a
        reference hostfile; returns the winning ds_config. The same
        search dims and memory-budget pruning as :meth:`tune` apply —
        estimator-rejected candidates are recorded without being
        scheduled. Requires ``model_spec`` (+ optional ``batch_spec``)
        — the out-of-process workers rebuild the model from the JSON
        spec."""
        from deepspeed_tpu.autotuning.scheduler import ResourceManager, parse_hostfile
        if self.model_spec is None:
            raise ValueError("tune_distributed needs model_spec (a JSON-able "
                             "exp_runner model description)")
        if hosts is None:
            hosts = parse_hostfile(hostfile) if hostfile else {"localhost": 1}
        if self.world_size is None:
            self.world_size = int(slots_per_exp)
        results_dir = self.results_dir or "autotuning_exps"
        self.results = []
        grid = []  # (stage, mbs, gas, offload, name, exp_dir)
        for stage in self.zero_stages:
            for offload in self.offload_candidates:
                for gas in self.gas_candidates:
                    for mbs in sorted(self.micro_batches):
                        if self.memory_budget_bytes is not None and \
                                self._prune_by_memory(stage, mbs, gas, offload) is not None:
                            continue
                        name = f"z{stage}_mbs{mbs}"
                        if gas is not None:
                            name += f"_gas{gas}"
                        if offload is not None:
                            name += f"_off{int(bool(offload))}"
                        exp_dir = os.path.join(results_dir, name)
                        os.makedirs(exp_dir, exist_ok=True)
                        exp = {"name": name,
                               "ds_config": self._experiment_config(stage, mbs, gas, offload),
                               "model": self.model_spec, "batch": self.batch_spec or {},
                               "steps": self.steps}
                        with open(os.path.join(exp_dir, "exp.json"), "w") as f:
                            json.dump(exp, f, indent=1)
                        grid.append((stage, mbs, gas, offload, name, exp_dir))
        rm = ResourceManager(hosts, results_dir, slots_per_exp=slots_per_exp,
                             env=env, timeout=timeout)
        rm.schedule_experiments([g[5] for g in grid])
        finished = rm.run()
        for stage, mbs, gas, offload, name, _ in grid:
            r = finished.get(name, {"value": None, "error": "never ran"})
            self.results.append({"zero_stage": stage, "micro_batch_size": mbs,
                                 "gas": gas, "offload": offload,
                                 "metric": self.metric, "value": r.get("value"),
                                 "error": r.get("error"),
                                 "step_time_s": r.get("step_time_s")})
        ok = [r for r in self.results if r["value"] is not None]
        if not ok:
            raise RuntimeError("autotuning: every experiment failed; see results")
        self.best = max(ok, key=lambda r: r["value"])
        self.results_dir = results_dir
        self.write_results()
        return self._experiment_config(self.best["zero_stage"],
                                       self.best["micro_batch_size"],
                                       self.best.get("gas"), self.best.get("offload"))

    def write_results(self):
        os.makedirs(self.results_dir, exist_ok=True)
        with open(os.path.join(self.results_dir, "autotuning_results.json"), "w") as f:
            json.dump(self.results, f, indent=1)
        best_cfg = self._experiment_config(self.best["zero_stage"], self.best["micro_batch_size"],
                                           self.best.get("gas"), self.best.get("offload"))
        with open(os.path.join(self.results_dir, "ds_config_optimal.json"), "w") as f:
            json.dump(best_cfg, f, indent=1)

    def print_tuning_results(self):
        print(f"{'stage':>6} {'micro_bs':>9} {'samples/s':>12}  error")
        for r in self.results:
            val = f"{r['value']:.1f}" if r["value"] is not None else "-"
            print(f"{r['zero_stage']:>6} {r['micro_batch_size']:>9} {val:>12}  "
                  f"{(r['error'] or '')[:60]}")


def autotune(model_fn, base_config, batch_fn, **kwargs):
    """One-call convenience: returns the tuned ds_config."""
    tuner = Autotuner(model_fn, base_config, batch_fn, **kwargs)
    return tuner.tune()
