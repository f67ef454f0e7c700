"""The graft-lint analysis core: pure-AST, no jax import.

Each rule family is a method suite on :class:`FileLinter`; a file is
parsed once and every rule walks the same tree. Violations carry a
``symbol`` (dotted class.function scope) so baseline entries survive
line-number drift.
"""

import ast
import json
import os
from collections import namedtuple

# Rule ids ----------------------------------------------------------------
JIT_PURITY = "jit-purity"
HOST_SYNC = "host-sync"
THREAD_SHARED = "thread-shared-state"
SPEC_CONSISTENCY = "spec-consistency"
ENV_REGISTRY = "env-registry"
LOCK_ORDER_RULE = "lock-order"
KNOB_DOCS = "knob-docs"  # cross-artifact rule, driven by cli.check_knob_docs
WIRE_CONTRACT = "wire-contract"  # cross-file parity over the process boundary
REPLAY_DETERMINISM = "replay-determinism"
RULES = (JIT_PURITY, HOST_SYNC, THREAD_SHARED, SPEC_CONSISTENCY,
         ENV_REGISTRY, LOCK_ORDER_RULE, KNOB_DOCS, WIRE_CONTRACT,
         REPLAY_DETERMINISM)

# Must mirror deepspeed_tpu/parallel/topology.py MESH_AXES — the linter
# cannot import the package (no jax at lint time); a unit test asserts
# the two stay in sync.
MESH_AXES = ("pipe", "data", "expert", "sequence", "tensor")

Violation = namedtuple("Violation", "rule path line col symbol message")

# ------------------------------------------------------------------ config
# Names whose call wraps a function for tracing (the first positional
# argument, or the decorated function).
_JIT_WRAPPERS = {"jit", "pjit", "shard_map", "pallas_call",
                 "shard_map_kernel", "maybe_checkify_jit", "checkify"}

# host-sync scope: file suffix -> traced-hot-path qualnames. These are
# the serving paths where one stray sync serializes the pipeline.
_HOT_PATHS = {
    "inference/v2/scheduler.py": {
        "DynamicSplitFuseScheduler._plan",
        "DynamicSplitFuseScheduler._try_burst",
        "DynamicSplitFuseScheduler._try_spec_burst",
        "DynamicSplitFuseScheduler.step",
        "DynamicSplitFuseScheduler._step",
        "DynamicSplitFuseScheduler.hand_over",
        # the planner and the accept side every burst runs through, and
        # the pipeline (async_burst.depth > 0): a stray sync here stalls
        # the double buffer — the ONE intended sync lives in
        # AsyncBurstHandle.fetch, reached via _fence_one
        "DynamicSplitFuseScheduler._plan_burst",
        "DynamicSplitFuseScheduler._accept_burst",
        "DynamicSplitFuseScheduler._accept_token",
        "DynamicSplitFuseScheduler._fence_one",
        "DynamicSplitFuseScheduler._drain_pipeline",
    },
    "serving/gateway.py": {
        "ServingGateway._pump_once",
        "ServingGateway._admit",
        "ServingGateway._step",
        "ServingGateway._process_cancels",
        "ServingGateway._process_deadlines",
        "ServingGateway._resume_paused",
        "ServingGateway._retire",
        "ServingGateway._on_tokens",
    },
    "inference/v2/engine_v2.py": {
        "InferenceEngineV2.put",
        "InferenceEngineV2.decode_burst",
        "InferenceEngineV2.decode_burst_async",
        "InferenceEngineV2._dispatch_burst",
        "InferenceEngineV2.verify_burst",
        "AsyncBurstHandle.fetch",
    },
}

# Calls that force a device→host sync (or a host copy of device data).
_SYNC_ATTRS = {"item", "block_until_ready"}
_SYNC_DOTTED = {"jax.device_get", "jax.block_until_ready",
                "np.asarray", "np.array", "numpy.asarray", "numpy.array"}
# float()/bool() on an array force a sync; int() is deliberately NOT
# flagged — the hot paths do int() on host-side allocator bookkeeping
# constantly, and int() on a device array shows up via the np.* /
# .item() patterns above anyway.
_SYNC_BUILTINS = {"float", "bool"}

# thread-shared-state registry: class -> attributes mutated by more
# than one thread. Writes outside ``with self.<*lock*>:`` are flagged
# (``__init__`` is exempt — the object is not yet published).
THREAD_SHARED_REGISTRY = {
    "ServingGateway": {"_cancels", "_state", "_pump_stop", "_handoffs",
                       "_pending_refresh"},
    "NebulaCheckpointService": {"_pending_job", "_failure", "_last_persist",
                                "_stats", "_thread"},
    "MonitorMaster": {"backends"},
    "ServingMetrics": {"_counters", "_gauges", "_external"},
    "BlockedAllocator": {"_free", "_free_set"},
    "PrefixCacheManager": {"_leases", "lookups", "hits", "tokens_saved",
                           "insertions", "tier", "tier2_hits",
                           "tier2_tokens_saved"},
    # kv tier: the prefetch worker stages/claims against state the pump
    # thread (demote/promote) and client threads (prefetch kick, stats)
    # also mutate
    "TierManager": {"_staged", "_inflight", "demoted_blocks",
                    "promoted_blocks", "prefetched_blocks", "stage_hits",
                    "prefetch_waits", "prefetch_wait_ms",
                    "prefetch_timeouts", "prefetch_errors",
                    "quant_error_max", "exported_blocks", "imported_blocks",
                    "import_rejects"},
    "HostKVStore": {"_records", "bytes_resident", "demotions", "promotions",
                    "evictions", "lookups", "hits"},
    # multi-tenant LoRA: the adapter prefetch worker stages slabs while
    # the pump thread binds/promotes/evicts and client threads register,
    # publish, prefetch-kick, and read stats
    "AdapterStore": {"_hot", "_slot_meta", "_refs", "_uid_slot", "_lru",
                     "_free", "_host", "_host_bytes", "_staged", "_inflight",
                     "_a", "_b", "_scales", "_shutdown",
                     "registrations", "promotions", "evictions",
                     "host_evictions", "hot_hits", "hot_misses", "swaps",
                     "prefetched", "stage_hits", "prefetch_errors",
                     "publish_rejects"},
    # structured decoding: every gateway's client submit threads compile
    # schemas through the ONE process-wide cache at admission, so the
    # LRU map and its counters are cross-thread state
    "SchemaCompilerCache": {"_cache", "compiles", "hits"},
    # spec decode: the gateway pump drafts/notes while client threads
    # reach forget() through engine.flush (cancel / deadline / drain),
    # and the online SLO controller adjusts draft_len_cfg live
    "SpecDecodeState": {"_ema", "_disabled", "steps", "accepted", "drafted",
                        "emitted", "disables", "draft_len_cfg"},
    # serving autotuner: the controller thread mutates decision state
    # while operator threads read stats()/reset(); the trace recorder
    # is written from every client thread that submits
    "OnlineSLOController": {"_breach", "_clear", "_cooldown", "_frozen",
                            "_last_action", "_clear_required",
                            "_last_up_tick", "ticks", "adjustments",
                            "rollbacks"},
    "TraceRecorder": {"_t0", "_requests", "_groups", "recorded"},
    # fleet: relay threads + heartbeat thread + client threads all touch
    # router/health/replica state
    "FleetRouter": {"_counters", "_relays", "_closed"},
    # wire transport: the supervisor monitor thread relaunches children
    # while operator threads kill/stop/query; the client's reader thread
    # demuxes into state client threads register/release; the server's
    # accept/dispatch/relay threads share conn + stream registries
    "FleetSupervisor": {"_children", "_stopped", "restarts_total"},
    "WireReplica": {"_sock", "_wfile", "_reader", "_pending", "_next_rid",
                    "_backoff", "_retry_at", "_closed", "reconnects"},
    "ReplicaServer": {"_state", "_conns", "_streams", "served"},
    "ReplicaHealth": {"_state", "_consecutive_failures", "_half_open_ok",
                      "_next_probe_at", "_probe_backoff", "transitions"},
    "GatewayReplica": {"gateway", "restarts"},
    "FaultyReplica": {"_killed", "_reject_left", "_submits",
                      "_claimed_version"},
    # live weight refresh: rollouts run on an operator/train thread
    # while relay threads read versions and the publisher may be shared
    # with a bench/train loop publishing concurrently
    "WeightPublisher": {"publishes", "rejects"},
    "FleetRefreshController": {"current_version", "current_chain",
                               "_adopted_params", "rollouts"},
    # disagg serving: relay threads publish/claim handoffs and note
    # pool outcomes concurrently; the router snapshot reads both
    "HandoffManager": {"_inflight", "published", "delivered", "acked",
                       "failed", "expired"},
    "PoolScheduler": {"mode", "_consecutive_failures",
                      "_consecutive_successes", "_requests_while_degraded",
                      "degraded_entries", "degraded_exits", "transitions"},
    # preemption: the signal handler and the training thread race on the
    # request flag; the heartbeat is beaten from the training thread and
    # read by the agent process (file) but its bookkeeping is shared
    # with any in-process watchdog probes
    "PreemptionGuard": {"_requested", "_requested_at"},
    "HeartbeatWriter": {"_last_step", "_last_beat_t"},
    # grouped GEMM dispatch telemetry: serving traces from gateway pump
    # threads while bench/test readers snapshot from the main thread
    "GroupedGemmStats": {"_counts"},
}

_MUTATORS = {"append", "extend", "insert", "remove", "pop", "clear",
             "update", "add", "discard", "setdefault", "popitem",
             "difference_update", "appendleft"}

# lock-order: the canonical acquisition order, as CODE. A lock may only
# be taken while holding locks of strictly LOWER rank; an edge from a
# higher rank to a lower one is a deadlock-shaped inversion. The two
# documented orders this encodes: router -> gateway -> engine-side
# caches, and the kv-tier stack ``manager._lock -> tier._lock ->
# store._lock`` (tier_manager.py module docstring). Locks not listed
# here are "unranked": edges touching them are still collected and
# checked for cycles, just not against a rank.
LOCK_ORDER = {
    # the refresh controller orchestrates ABOVE the router (it calls
    # router counters/health and replica refresh while holding its
    # lock), and calls into its publisher, so both rank below rank 10
    "FleetRefreshController._lock": 4,
    "WeightPublisher._lock": 6,
    # the fleet supervisor is an outermost orchestrator: its monitor
    # thread only spawns/kills OS processes and never calls into the
    # router, but operator code may stop the fleet while holding no
    # other lock — rank it above (outside) the router
    "FleetSupervisor._lock": 8,
    "FleetRouter._lock": 10,
    # the wire client is called FROM router relay threads (rank 10) and
    # itself takes only its own lock (socket I/O happens outside it)
    "WireReplica._lock": 12,
    "HandoffManager._lock": 14,
    "PoolScheduler._lock": 16,
    # the replica server dispatches into the gateway (ranks 20+) while
    # holding nothing; its own lock guards only conn/stream registries
    "ReplicaServer._lock": 17,
    # the online SLO controller decides under its own lock and actuates
    # gateway knobs outside it, so it ranks between the router and the
    # gateway's own locks; the trace recorder is a leaf (submit-path
    # append, never holds anything else)
    "OnlineSLOController._lock": 18,
    "TraceRecorder._lock": 19,
    "ServingGateway._handoff_lock": 20,
    "ServingGateway._cancel_lock": 22,
    "ServingGateway._state_lock": 24,
    # staged-refresh handshake: always held alone on the caller side;
    # the pump takes it strictly before/after (never around) the swap
    "ServingGateway._refresh_lock": 26,
    "PrefixCacheManager._lock": 30,
    # the adapter store is called from the pump with no engine-side lock
    # held above it, and itself calls only its publisher (unranked leaf
    # I/O) — it slots between the prefix cache and the kv-tier stack
    "AdapterStore._lock": 34,
    # the schema compiler cache is a leaf: get_or_compile runs the
    # compiler OUTSIDE the lock and the locked sections touch only the
    # LRU map — it never calls into another registered class
    "SchemaCompilerCache._lock": 36,
    "TierManager._lock": 40,
    "HostKVStore._lock": 50,
}

# lock-order: which self-attributes point at OTHER registered classes,
# so ``with self.manager._lock:`` / ``mgr = self.manager; with
# mgr._lock:`` and ``self.tier.demote(...)`` resolve to the peer
# class's locks one call level deep.
CROSS_REFS = {
    "PrefixCacheManager": {"tier": "TierManager"},
    "TierManager": {"manager": "PrefixCacheManager", "store": "HostKVStore"},
    "FleetRouter": {"handoffs": "HandoffManager", "pools": "PoolScheduler"},
    "FleetRefreshController": {"router": "FleetRouter",
                               "publisher": "WeightPublisher"},
    "OnlineSLOController": {"gateway": "ServingGateway"},
}

# lock-order: per registered class, the methods a PEER may call and the
# lock keys each acquires (its own and, one level deep, the locks of
# the objects it calls into). A cross-object call into one of these
# while holding a lock contributes acquisition edges. The table is kept
# honest by an in-file drift check (run only on the class's home file,
# LOCKING_METHODS_HOME): a declared method that no longer exists, a
# direct self-lock acquisition it fails to declare, or a new public
# locking method missing from the table are all lock-order violations.
LOCKING_METHODS = {
    "TierManager": {
        "demote": ("TierManager._lock", "HostKVStore._lock"),
        "probe_chain": ("TierManager._lock", "HostKVStore._lock"),
        "claim": ("TierManager._lock", "HostKVStore._lock"),
        "unclaim": ("HostKVStore._lock",),
        "note_promoted": ("TierManager._lock",),
        "export_chain": ("PrefixCacheManager._lock", "TierManager._lock"),
        "import_chain": ("TierManager._lock", "HostKVStore._lock"),
        "invalidate": ("TierManager._lock", "HostKVStore._lock"),
        "prefetch": ("TierManager._lock", "TierManager._queue_ready"),
        "wait_prefetch": ("TierManager._lock",),
        "shutdown": ("TierManager._queue_ready", "TierManager._lock",
                     "HostKVStore._lock"),
        "stats": ("TierManager._lock", "HostKVStore._lock"),
    },
    "HostKVStore": {
        "put": ("HostKVStore._lock",),
        "pop": ("HostKVStore._lock",),
        "peek": ("HostKVStore._lock",),
        "contains": ("HostKVStore._lock",),
        "clear": ("HostKVStore._lock",),
        "stats": ("HostKVStore._lock",),
    },
    "PrefixCacheManager": {
        "attach_tier": ("PrefixCacheManager._lock",),
        "ensure_free": ("PrefixCacheManager._lock",),
        "reserve": ("PrefixCacheManager._lock",),
        "acquire": ("PrefixCacheManager._lock", "TierManager._lock",
                    "HostKVStore._lock"),
        "match_len": ("PrefixCacheManager._lock", "TierManager._lock",
                      "HostKVStore._lock"),
        "release_lease": ("PrefixCacheManager._lock",),
        "snapshot_of": ("PrefixCacheManager._lock",),
        "snapshot_slot": ("PrefixCacheManager._lock",),
        "release": ("PrefixCacheManager._lock", "TierManager._lock",
                    "HostKVStore._lock"),
        "invalidate_for_version": ("PrefixCacheManager._lock",
                                   "TierManager._lock",
                                   "HostKVStore._lock"),
    },
}

# Drift-check scope: the file that actually defines each class above.
# Fixture/test files re-declaring the class name are not held to the
# table (they exercise the analysis, not the real inventory).
LOCKING_METHODS_HOME = {
    "TierManager": "inference/v2/kv_tier/tier_manager.py",
    "HostKVStore": "inference/v2/kv_tier/host_store.py",
    "PrefixCacheManager": "inference/v2/prefix_cache/manager.py",
}

# lock-order: registered-class methods that can BLOCK (fence waits,
# worker joins) — calling one through a cross-ref while holding any
# lock is a blocking-under-lock violation even though the blocking call
# itself is one level down.
BLOCKING_METHODS = {
    "TierManager": {"wait_prefetch", "shutdown"},
    "ServingGateway": {"drain", "close"},
    "FleetRouter": {"drain", "shutdown"},
}

# Blocking-call heuristics for the in-method walk.
_BLOCKING_DOTTED = {"jax.device_get", "jax.block_until_ready",
                    "subprocess.run", "subprocess.call",
                    "subprocess.check_call", "subprocess.check_output",
                    "os.waitpid"}
_JOIN_RECEIVER_HINTS = ("thread", "worker", "relay", "pump", "agent")
_SLEEP_UNDER_LOCK_THRESHOLD_S = 0.01

# spec-consistency dtype-leak scope (fp32 Python constants materialized
# as arrays in bf16 arithmetic): kernel and model code only (plus the
# grouped-GEMM dispatch, which sits one level up from ops/pallas but
# builds the kernel's padded layouts in the activation dtype).
_DTYPE_DIRS = ("ops/pallas/", "models/", "ops/grouped_gemm")
_JNP_CTORS = {"jnp.array": 2, "jnp.asarray": 2, "jnp.ones": 2,
              "jnp.zeros": 2, "jnp.full": 3}  # value -> positional arity
#  with dtype

# wire-contract: the files whose hand-maintained agreement IS the
# cross-process protocol. Suffix-matched (like _HOT_PATHS) so fixture
# mirrors under a tmp root are held to the same contract in tests.
_WIRE_REPLICA_FILE = "serving/fleet/replica.py"
_WIRE_CLIENT_FILE = "serving/fleet/wire/client.py"
_WIRE_SERVER_FILE = "serving/fleet/wire/server.py"
_WIRE_ERRORS_FILE = "serving/fleet/wire/errors.py"

# Wire ops with no same-named abstract Replica method: ``cancel`` is
# handle-level (client side lives on _WireHandle, server side on the
# stream registry), so it is exempt from the method<->op parity check
# but still held to client<->server parity.
_WIRE_HANDLE_OPS = {"cancel"}

# Codec-send call names whose dict arguments must be literal-keyed
# wire-safe payloads (checked on the wire client/server files only).
_WIRE_SEND_FUNCS = {"write_frame", "send", "_send", "_safe_send"}

# replay-determinism scope: file suffix -> REPLAY_CRITICAL qualnames.
# Everything listed here feeds bit-identical replay — failover replay
# verification, disagg continuation verify, refresh canary compare,
# autotune trace replay — so any nondeterminism (unseeded RNG, wall
# clock flowing into token-visible state, unordered set iteration,
# salted hashes) silently breaks exactness fleet-wide. An entry may be
# a function, a ``Class.method``, a class name (every method is then
# critical), or ``"*"`` (the whole module). Rationale per entry lives
# in docs/LINTING.md.
REPLAY_CRITICAL = {
    "inference/v2/engine_v2.py": {
        "InferenceEngineV2.put",
        "InferenceEngineV2.decode_burst",
        "InferenceEngineV2.decode_burst_async",
        "InferenceEngineV2._dispatch_burst",
        "InferenceEngineV2.verify_burst",
        "InferenceEngineV2.draw_seed",
        "AsyncBurstHandle.fetch",
    },
    "inference/v2/scheduler.py": {
        "DynamicSplitFuseScheduler._plan",
        "DynamicSplitFuseScheduler._try_burst",
        "DynamicSplitFuseScheduler._try_spec_burst",
        "DynamicSplitFuseScheduler._plan_burst",
    },
    "inference/structured/prng.py": {"*"},
    "inference/structured/sampling.py": {"*"},
    "inference/v2/kv_tier/tier_manager.py": {
        "TierManager.export_chain",
        "TierManager.import_chain",
    },
    "serving/fleet/handoff.py": {"HandoffManager"},
    "serving/fleet/router.py": {
        "FleetRouter._serve",
        "FleetRouter._serve_disagg",
        "FleetRouter._attempt",
        "FleetRouter._backoff",
    },
    "autotuning/trace.py": {
        "synthesize_trace",
        "replay_lockstep",
        "replay_realtime",
    },
}

# Wall-clock reads that are nondeterministic across replays.
_REPLAY_WALL_CLOCK = {"time.time", "time.time_ns", "time.monotonic",
                      "time.monotonic_ns", "time.perf_counter",
                      "time.perf_counter_ns", "time.process_time",
                      "datetime.now", "datetime.datetime.now",
                      "datetime.utcnow", "datetime.datetime.utcnow"}
# Deadline/metrics idiom: a clock read assigned to a *-named local (or
# combined arithmetically / compared — elapsed math and deadline checks)
# never reaches token-visible state; anything else in a REPLAY_CRITICAL
# scope is flagged.
_CLOCK_IDIOM_NAMES = ("deadline", "timeout", "expire", "until", "retry",
                      "start", "t0", "now", "beat", "elapsed", "wall")
# Seeded RNG constructors: allowed in REPLAY_CRITICAL scope when given
# an explicit seed argument.
_SEEDED_RNG_CTORS = {"Random", "default_rng", "RandomState", "Generator"}


# ----------------------------------------------------------------- helpers
def _dotted(node):
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last(dotted):
    return dotted.rsplit(".", 1)[-1] if dotted else None


def _self_attr(node):
    """'attr' when node is ``self.attr`` (unwrapping subscripts:
    ``self.attr[k]`` → 'attr'), else None."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name) and node.value.id == "self"):
        return node.attr
    return None


def _has_float_literal(node):
    """True when node is/contains a non-bool float constant (the thing
    that silently materializes as fp32)."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, float)
    if isinstance(node, ast.UnaryOp):
        return _has_float_literal(node.operand)
    if isinstance(node, (ast.List, ast.Tuple)):
        return any(_has_float_literal(e) for e in node.elts)
    return False


def _parse_pragmas(source):
    """line -> set of disabled rule names ('all' disables everything).
    A pragma on its own line applies to the next line too."""
    pragmas = {}
    for lineno, text in enumerate(source.splitlines(), start=1):
        idx = text.find("# ds-lint:")
        if idx < 0:
            continue
        body = text[idx + len("# ds-lint:"):]
        body = body.split("--", 1)[0]  # strip the reason
        body = body.strip()
        if not body.startswith("disable="):
            continue
        rules = {r.strip() for r in body[len("disable="):].split(",") if r.strip()}
        pragmas.setdefault(lineno, set()).update(rules)
        if text[:idx].strip() == "":  # standalone pragma line
            pragmas.setdefault(lineno + 1, set()).update(rules)
    return pragmas


class BaselineError(ValueError):
    """Malformed or unsupported baseline.json (typed so the CLI can
    turn it into a clean exit-2 instead of a traceback)."""


def load_baseline(path):
    """tools/graft_lint/baseline.json → set of (rule, path, symbol)
    triples. Line numbers are deliberately not part of the key."""
    with open(path) as fd:
        try:
            data = json.load(fd)
        except json.JSONDecodeError as e:
            raise BaselineError(f"baseline {path} is not valid JSON: {e}")
    if not isinstance(data, dict):
        raise BaselineError(f"baseline {path} must be a JSON object, "
                            f"got {type(data).__name__}")
    if data.get("version") != 1:
        raise BaselineError(f"unsupported baseline version in {path}")
    entries = data.get("suppressions", ())
    if not isinstance(entries, list):
        raise BaselineError(f"baseline {path} 'suppressions' must be a list")
    out = set()
    for e in entries:
        if not isinstance(e, dict) or "rule" not in e or "path" not in e:
            raise BaselineError(f"baseline {path} entry {e!r} needs "
                                f"'rule' and 'path' keys")
        out.add((e["rule"], e["path"], e.get("symbol", "")))
    return out


# --------------------------------------------------------------- the pass
class FileLinter:

    def __init__(self, path, source, relpath=None):
        self.path = path
        # rule scoping matches on /-separated relative paths
        self.relpath = (relpath or path).replace(os.sep, "/")
        self.source = source
        self.tree = ast.parse(source, filename=path)
        self.violations = []
        # surviving lock-acquisition edges (rank-clean, unpragma'd) for
        # the cross-file cycle pass run by lint_paths/lint_file
        self.lock_edges = []
        # per-file wire-contract facts (op tables, relay methods, error
        # classes) for the cross-file parity pass; filled by
        # check_wire_contract, merged by wire_contract_violations
        self.wire_info = None
        # parent / scope bookkeeping filled by _annotate
        self._parents = {}
        self._qualnames = {}
        self._traced = set()  # FunctionDef/Lambda nodes traced by jit
        self._annotate()

    # -- tree annotation ---------------------------------------------------
    def _annotate(self):
        defs_by_name = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs_by_name.setdefault(node.name, []).append(node)
        # dotted scope names
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                parts = [node.name]
                p = self._parents.get(node)
                while p is not None:
                    if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                        parts.append(p.name)
                    p = self._parents.get(p)
                self._qualnames[node] = ".".join(reversed(parts))

        # traced functions: decorated with a jit wrapper, or passed as
        # the first argument to one
        roots = set()
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    if _last(_dotted(target)) in _JIT_WRAPPERS:
                        roots.add(node)
            if isinstance(node, ast.Call) and \
                    _last(_dotted(node.func)) in _JIT_WRAPPERS and node.args:
                wrapped = node.args[0]
                if isinstance(wrapped, ast.Lambda):
                    roots.add(wrapped)
                elif isinstance(wrapped, ast.Name):
                    for d in defs_by_name.get(wrapped.id, ()):
                        roots.add(d)
        # everything defined inside a traced function traces with it
        for root in roots:
            for sub in ast.walk(root):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                    self._traced.add(sub)
        self._traced |= roots
        self._traced_roots = roots

    def _qualname(self, node):
        return self._qualnames.get(node, "<module>")

    def _enclosing_symbol(self, node):
        p = node
        while p is not None:
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                return self._qualname(p)
            p = self._parents.get(p)
        return "<module>"

    def _emit(self, rule, node, message):
        self.violations.append(Violation(
            rule=rule, path=self.relpath, line=node.lineno,
            col=getattr(node, "col_offset", 0),
            symbol=self._enclosing_symbol(node), message=message))

    # -- rule 1: jit-purity ------------------------------------------------
    def check_jit_purity(self):
        for fn in self._traced:
            # Only the ROOT traced function's params are definitely
            # tracers. Nested-def params are often static metadata bound
            # through jax.tree.map (partition dims, config), so the
            # branch check stays root-only; side-effect checks apply to
            # the whole traced subtree.
            params = set()
            if fn in self._traced_roots:
                args = fn.args
                for a in (args.posonlyargs + args.args + args.kwonlyargs
                          + ([args.vararg] if args.vararg else [])
                          + ([args.kwarg] if args.kwarg else [])):
                    params.add(a.arg)
                params.discard("self")
            for node in ast.walk(fn):
                if node is fn:
                    continue
                # nested defs/lambdas are traced too and get their own
                # iteration — only check nodes fn directly owns
                if self._owner_fn(node) is not fn:
                    continue
                self._check_purity_node(fn, node, params)

    def _owner_fn(self, node):
        p = self._parents.get(node)
        while p is not None:
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                return p
            p = self._parents.get(p)
        return None

    def _check_purity_node(self, fn, node, params):
        if isinstance(node, ast.Call):
            dotted = _dotted(node.func)
            root = dotted.split(".", 1)[0] if dotted else None
            if root in ("time", "random") or (
                    dotted and dotted.startswith(("np.random.",
                                                  "numpy.random."))):
                self._emit(JIT_PURITY, node,
                           f"call to {dotted}() inside a traced function "
                           f"runs at TRACE time only (or reorders under "
                           f"compilation) — hoist it out of the jitted "
                           f"region")
            elif dotted == "print":
                self._emit(JIT_PURITY, node,
                           "print() inside a traced function fires at "
                           "trace time only; use jax.debug.print")
            elif dotted == "os.getenv":
                self._emit(JIT_PURITY, node,
                           "os.getenv inside a traced function is a "
                           "trace-time constant; read it before tracing")
        if isinstance(node, ast.Attribute) and \
                _dotted(node) == "os.environ":
            self._emit(JIT_PURITY, node,
                       "os.environ inside a traced function is a "
                       "trace-time constant; read it before tracing")
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                for el in (t.elts if isinstance(t, ast.Tuple) else [t]):
                    if _self_attr(el) is not None:
                        self._emit(JIT_PURITY, node,
                                   f"mutation of self.{_self_attr(el)} "
                                   f"inside a traced function happens at "
                                   f"trace time, not per call")
        if isinstance(node, (ast.If, ast.While)):
            if self._branches_on_param(node.test, params):
                kind = "if" if isinstance(node, ast.If) else "while"
                self._emit(JIT_PURITY, node,
                           f"Python `{kind}` on a traced argument forces "
                           f"concretization (TracerBoolConversionError at "
                           f"runtime); use lax.cond/jnp.where")

    def _branches_on_param(self, test, params):
        """Bare-name truthiness / value comparison on a traced parameter.
        Identity and containment checks (``is None``, ``in``) are static
        pytree-structure tests and stay legal."""
        if isinstance(test, ast.Name):
            return test.id in params
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._branches_on_param(test.operand, params)
        if isinstance(test, ast.BoolOp):
            return any(self._branches_on_param(v, params) for v in test.values)
        if isinstance(test, ast.Compare):
            if any(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                   for op in test.ops):
                return False
            return any(isinstance(e, ast.Name) and e.id in params
                       for e in [test.left] + test.comparators)
        return False

    # -- rule 2: host-sync -------------------------------------------------
    def check_host_sync(self):
        hot = None
        for suffix, names in _HOT_PATHS.items():
            if self.relpath.endswith(suffix):
                hot = names
                break
        if hot is None:
            return
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if self._qualname(node) not in hot:
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                dotted = _dotted(sub.func)
                if isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr in _SYNC_ATTRS:
                    self._emit(HOST_SYNC, sub,
                               f".{sub.func.attr}() in a serving hot path "
                               f"blocks on the device — keep this path "
                               f"async")
                elif dotted in _SYNC_DOTTED:
                    self._emit(HOST_SYNC, sub,
                               f"{dotted}() in a serving hot path copies "
                               f"device data to host (implicit sync)")
                elif dotted in _SYNC_BUILTINS and sub.args and isinstance(
                        sub.args[0], (ast.Name, ast.Attribute, ast.Subscript)):
                    self._emit(HOST_SYNC, sub,
                               f"{dotted}() on an array in a serving hot "
                               f"path forces a device sync")

    # -- rule 3: thread-shared-state --------------------------------------
    def check_thread_shared(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            attrs = THREAD_SHARED_REGISTRY.get(node.name)
            if not attrs:
                continue
            for method in node.body:
                if not isinstance(method, (ast.FunctionDef,
                                           ast.AsyncFunctionDef)):
                    continue
                if method.name == "__init__":
                    continue  # not yet published to other threads
                self._check_method_writes(method, attrs)

    def _check_method_writes(self, method, attrs):
        for node in ast.walk(method):
            written = None
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    for el in (t.elts if isinstance(t, ast.Tuple) else [t]):
                        a = _self_attr(el)
                        if a in attrs:
                            written = a
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _MUTATORS:
                a = _self_attr(node.func.value)
                if a in attrs:
                    written = a
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    a = _self_attr(t)
                    if a in attrs:
                        written = a
            if written is not None and not self._under_lock(node):
                self._emit(THREAD_SHARED, node,
                           f"write to shared self.{written} outside a "
                           f"`with self.<lock>:` block "
                           f"(class is touched by multiple threads)")

    def _under_lock(self, node):
        p = self._parents.get(node)
        while p is not None:
            if isinstance(p, ast.With):
                for item in p.items:
                    ctx = item.context_expr
                    if isinstance(ctx, ast.Call):
                        ctx = ctx.func  # e.g. self._lock.acquire_timeout()
                    d = _dotted(ctx)
                    if d and d.startswith("self.") and "lock" in d.lower():
                        return True
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break  # don't credit an outer function's lock
            p = self._parents.get(p)
        return False

    # -- rule 4: spec-consistency ------------------------------------------
    def check_spec_consistency(self):
        spec_ctors = {"PartitionSpec"}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if alias.name == "PartitionSpec" and alias.asname:
                        spec_ctors.add(alias.asname)
        allowed = set(MESH_AXES)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _last(_dotted(node.func))
            if name in spec_ctors:
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    for el in (arg.elts if isinstance(arg, (ast.Tuple,
                                                            ast.List))
                               else [arg]):
                        if isinstance(el, ast.Constant) and \
                                isinstance(el.value, str) and \
                                el.value not in allowed:
                            self._emit(SPEC_CONSISTENCY, el,
                                       f"PartitionSpec axis {el.value!r} is "
                                       f"not a declared mesh axis "
                                       f"{MESH_AXES}")
            if any(self.relpath.rpartition("deepspeed_tpu/")[2]
                   .startswith(d) for d in _DTYPE_DIRS):
                dotted = _dotted(node.func)
                arity = _JNP_CTORS.get(dotted)
                if arity is not None and len(node.args) < arity and \
                        not any(kw.arg == "dtype" for kw in node.keywords):
                    value_args = node.args[-1:] if dotted == "jnp.full" \
                        else node.args[:1]
                    if any(_has_float_literal(a) for a in value_args):
                        self._emit(SPEC_CONSISTENCY, node,
                                   f"{dotted}() on a float literal without "
                                   f"dtype= materializes fp32 and promotes "
                                   f"bf16 arithmetic — pass dtype explicitly")

    # -- rule 5: env-registry ----------------------------------------------
    def check_env_registry(self):
        if self.relpath.endswith("utils/env_registry.py"):
            return
        for node in ast.walk(self.tree):
            key = None
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                if dotted in ("os.environ.get", "os.getenv") and node.args:
                    key = node.args[0]
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.ctx, ast.Load) and \
                    _dotted(node.value) == "os.environ":
                key = node.slice
            elif isinstance(node, ast.Compare) and \
                    len(node.ops) == 1 and \
                    isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
                    _dotted(node.comparators[0]) == "os.environ":
                key = node.left
            if isinstance(key, ast.Constant) and \
                    isinstance(key.value, str) and \
                    key.value.startswith("DS_"):
                self._emit(ENV_REGISTRY, node,
                           f"read of {key.value} bypasses "
                           f"deepspeed_tpu/utils/env_registry.py — use "
                           f"env_bool/env_int/env_str/env_raw")

    # -- rule 6: lock-order ------------------------------------------------
    def check_lock_order(self):
        """Per registered class, walk each method with a held-lock stack
        and (a) emit acquisition edges checked against LOCK_ORDER (rank
        inversions flagged here; surviving edges collected on
        ``self.lock_edges`` for cross-file cycle detection), (b) flag
        blocking calls reached while any lock is held, (c) flag
        re-acquisition of a non-reentrant lock, (d) keep the declared
        LOCKING_METHODS table honest on each class's home file."""
        for cls in ast.walk(self.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            if cls.name not in THREAD_SHARED_REGISTRY:
                continue
            locks, cond_target = self._discover_locks(cls)
            methods = [m for m in cls.body
                       if isinstance(m, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
            summaries = {m.name: self._method_lock_summary(cls.name, m, locks,
                                                           cond_target)
                         for m in methods}
            self._check_locking_methods_drift(cls, methods, summaries)
            for method in methods:
                if method.name == "__init__":
                    continue  # not yet published; lock wiring lives here
                ctx = {"cls": cls.name, "locks": locks,
                       "cond_target": cond_target, "aliases": {},
                       "held": [], "summaries": summaries}
                if method.name.endswith("_locked") and "_lock" in locks:
                    # caller-holds-the-lock convention: analyze the body
                    # as if the class's primary lock is already held
                    ctx["held"].append({"key": f"{cls.name}._lock",
                                        "kind": locks["_lock"],
                                        "seed": True})
                self._walk_lock_stmts(method.body, ctx)

    # lock discovery -------------------------------------------------------
    def _discover_locks(self, cls):
        """``__init__`` assignments → {attr: 'lock'|'rlock'|'condition'}
        plus {condition attr: underlying lock attr} (a ``Condition(self.X)``
        aliases X; a bare ``Condition()`` owns its lock — reentrant).
        ``tracked_lock(...)`` wrappers (the DS_SANITIZE runtime twin) are
        unwrapped to the real constructor."""
        locks, cond_target = {}, {}
        init = next((m for m in cls.body if isinstance(m, ast.FunctionDef)
                     and m.name == "__init__"), None)
        if init is None:
            return locks, cond_target
        for node in ast.walk(init):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            attr = _self_attr(node.targets[0])
            if attr is None:
                continue
            value = node.value
            if isinstance(value, ast.Call) and \
                    _last(_dotted(value.func)) == "tracked_lock" and value.args:
                value = value.args[0]
            if not isinstance(value, ast.Call):
                continue
            ctor = _last(_dotted(value.func))
            if ctor == "Lock":
                locks[attr] = "lock"
            elif ctor == "RLock":
                locks[attr] = "rlock"
            elif ctor == "Condition":
                locks[attr] = "condition"
                tgt = _self_attr(value.args[0]) if value.args else None
                cond_target[attr] = tgt if tgt else attr
        return locks, cond_target

    def _resolve_lock(self, expr, ctx):
        """→ (lock key 'Class.attr', kind, local attr) or None. Handles
        ``self.X`` (declared locks and *lock*-named fallbacks),
        ``self.ref._lock`` through CROSS_REFS, local object/lock
        aliases, and ``self.X.acquire*()`` call forms."""
        if isinstance(expr, ast.Call):
            f = expr.func
            if isinstance(f, ast.Attribute) and f.attr.startswith("acquire"):
                expr = f.value
            else:
                return None
        d = _dotted(expr)
        if d is None:
            return None
        parts = d.split(".")
        locks, cond_target = ctx["locks"], ctx["cond_target"]
        if parts[0] == "self" and len(parts) == 2:
            attr = parts[1]
            if attr in locks:
                target = cond_target.get(attr, attr)
                kind = locks.get(target, locks[attr])
                return (f"{ctx['cls']}.{target}", kind, attr)
            if "lock" in attr.lower():
                return (f"{ctx['cls']}.{attr}", "unknown", attr)
            return None
        if parts[0] == "self" and len(parts) == 3:
            peer = CROSS_REFS.get(ctx["cls"], {}).get(parts[1])
            if peer and "lock" in parts[2].lower():
                return (f"{peer}.{parts[2]}", "unknown", parts[2])
            return None
        if len(parts) == 2 and parts[0] in ctx["aliases"]:
            akind, val = ctx["aliases"][parts[0]]
            if akind == "obj" and "lock" in parts[1].lower():
                return (f"{val}.{parts[1]}", "unknown", parts[1])
            return None
        if len(parts) == 1 and parts[0] in ctx["aliases"]:
            akind, val = ctx["aliases"][parts[0]]
            if akind == "lock":
                return val
        return None

    def _resolve_peer(self, recv, ctx):
        """Receiver expression → peer registered class name, via
        CROSS_REFS (``self.tier``) or a tracked local alias."""
        d = _dotted(recv)
        if d is None:
            return None
        parts = d.split(".")
        if parts[0] == "self" and len(parts) == 2:
            return CROSS_REFS.get(ctx["cls"], {}).get(parts[1])
        if len(parts) == 1 and parts[0] in ctx["aliases"]:
            akind, val = ctx["aliases"][parts[0]]
            if akind == "obj":
                return val
        return None

    def _method_lock_summary(self, cls_name, method, locks, cond_target):
        """Locks this method DIRECTLY acquires (``with``/``.acquire()``
        on self locks) — the one-level summary intra-class calls and the
        LOCKING_METHODS drift check consume."""
        ctx = {"cls": cls_name, "locks": locks, "cond_target": cond_target,
               "aliases": {}}
        out = set()
        for node in ast.walk(method):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    res = self._resolve_lock(item.context_expr, ctx)
                    if res:
                        out.add(res[:2])
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "acquire":
                res = self._resolve_lock(node, ctx)
                if res:
                    out.add(res[:2])
        return out

    def _check_locking_methods_drift(self, cls, methods, summaries):
        declared = LOCKING_METHODS.get(cls.name)
        home = LOCKING_METHODS_HOME.get(cls.name)
        if not declared or not home or not self.relpath.endswith(home):
            return
        by_name = {m.name: m for m in methods}
        prefix = cls.name + "."
        for mname, keys in sorted(declared.items()):
            if mname not in by_name:
                self._emit(LOCK_ORDER_RULE, cls,
                           f"LOCKING_METHODS declares {cls.name}.{mname} "
                           f"which no longer exists — update the table in "
                           f"tools/graft_lint/linter.py")
                continue
            direct_self = {key for key, _kind in summaries.get(mname, ())
                           if key.startswith(prefix)}
            missing = direct_self - set(keys)
            if missing:
                self._emit(LOCK_ORDER_RULE, by_name[mname],
                           f"{cls.name}.{mname} acquires "
                           f"{sorted(missing)} not declared in "
                           f"LOCKING_METHODS — update the table")
        for mname, m in sorted(by_name.items()):
            if mname.startswith("_") or mname in declared:
                continue
            self_locks = {key for key, _kind in summaries.get(mname, ())
                          if key.startswith(prefix)}
            if self_locks:
                self._emit(LOCK_ORDER_RULE, m,
                           f"public locking method {cls.name}.{mname} "
                           f"(acquires {sorted(self_locks)}) is missing "
                           f"from LOCKING_METHODS — peers calling it "
                           f"under a lock would be invisible to the "
                           f"deadlock analysis")

    # held-stack statement walk -------------------------------------------
    def _walk_lock_stmts(self, stmts, ctx):
        for stmt in stmts:
            self._walk_lock_stmt(stmt, ctx)

    def _walk_lock_stmt(self, stmt, ctx):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs run later, not under these locks
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            pushed = 0
            for item in stmt.items:
                res = self._resolve_lock(item.context_expr, ctx)
                if res is not None:
                    self._note_acquisition(res, item.context_expr, ctx)
                    pushed += 1
                else:
                    self._scan_exprs(item.context_expr, ctx)
            self._walk_lock_stmts(stmt.body, ctx)
            for _ in range(pushed):
                ctx["held"].pop()
            return
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Name):
            self._track_alias(stmt, ctx)
        # scan this statement's own expressions (not nested blocks)
        for field, value in ast.iter_fields(stmt):
            if isinstance(value, ast.expr):
                self._scan_exprs(value, ctx)
            elif isinstance(value, list):
                for el in value:
                    if isinstance(el, ast.expr):
                        self._scan_exprs(el, ctx)
        # then recurse into nested statement blocks
        for field in ("body", "orelse", "finalbody"):
            block = getattr(stmt, field, None)
            if block:
                self._walk_lock_stmts(block, ctx)
        for handler in getattr(stmt, "handlers", ()):
            self._walk_lock_stmts(handler.body, ctx)

    def _track_alias(self, stmt, ctx):
        name = stmt.targets[0].id
        ctx["aliases"].pop(name, None)
        attr = _self_attr(stmt.value)
        if attr is None:
            return
        peer = CROSS_REFS.get(ctx["cls"], {}).get(attr)
        if peer is not None:
            ctx["aliases"][name] = ("obj", peer)
        elif attr in ctx["locks"] or "lock" in attr.lower():
            res = self._resolve_lock(stmt.value, ctx)
            if res is not None:
                ctx["aliases"][name] = ("lock", res)

    def _note_acquisition(self, res, node, ctx, via_call=False):
        key, kind, _attr = res
        held = ctx["held"]
        if any(e["key"] == key for e in held):
            if kind == "lock":
                self._emit(LOCK_ORDER_RULE, node,
                           f"re-acquisition of non-reentrant {key} while "
                           f"already held — this deadlocks (use an RLock "
                           f"or restructure)")
            held.append({"key": key, "kind": kind, "via_call": via_call})
            return
        for e in held:
            self._note_edge(e["key"], key, node, ctx)
        held.append({"key": key, "kind": kind, "via_call": via_call})

    def _note_edge(self, src, dst, node, ctx):
        if src == dst:
            return
        rs, rd = LOCK_ORDER.get(src), LOCK_ORDER.get(dst)
        if rs is not None and rd is not None and rs > rd:
            self._emit(LOCK_ORDER_RULE, node,
                       f"acquires {dst} while holding {src} — inverts the "
                       f"canonical lock order ({dst} rank {rd} is taken "
                       f"BEFORE {src} rank {rs}; see LOCK_ORDER in "
                       f"tools/graft_lint/linter.py)")
            return  # already reported; keep it out of the cycle graph
        self.lock_edges.append({
            "src": src, "dst": dst, "path": self.relpath,
            "line": node.lineno, "col": getattr(node, "col_offset", 0),
            "symbol": self._enclosing_symbol(node)})

    def _scan_exprs(self, expr, ctx):
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                self._scan_lock_call(node, ctx)

    def _scan_lock_call(self, call, ctx):
        held = ctx["held"]
        dotted = _dotted(call.func)
        if not isinstance(call.func, ast.Attribute):
            return
        meth = call.func.attr
        recv = call.func.value
        # explicit acquire()/release() pairs
        if meth == "acquire":
            res = self._resolve_lock(call, ctx)
            if res is not None:
                self._note_acquisition(res, call, ctx, via_call=True)
                return
        elif meth == "release":
            res = self._resolve_lock(
                ast.Call(func=ast.Attribute(value=recv, attr="acquire",
                                            ctx=ast.Load()),
                         args=[], keywords=[]), ctx)
            if res is not None:
                for i in range(len(held) - 1, -1, -1):
                    if held[i]["key"] == res[0] and held[i].get("via_call"):
                        del held[i]
                        break
                return
        if not held:
            return
        held_keys = [e["key"] for e in held]
        held_desc = ", ".join(dict.fromkeys(held_keys))
        # blocking-call heuristics ------------------------------------
        recv_d = (_dotted(recv) or "").lower()
        if meth == "join" and any(h in recv_d for h in _JOIN_RECEIVER_HINTS):
            self._emit(LOCK_ORDER_RULE, call,
                       f"Thread.join on {_dotted(recv)} while holding "
                       f"{held_desc} — joining a thread that may need the "
                       f"lock is a deadlock; join outside the lock")
            return
        if meth == "get" and not call.args and not call.keywords and \
                recv_d != "self":
            self._emit(LOCK_ORDER_RULE, call,
                       f"blocking .get() (no timeout) on {_dotted(recv)} "
                       f"while holding {held_desc}")
            return
        if meth == "wait" and not self._wait_is_timed(call):
            if not self._wait_is_condition_of_held(recv, ctx):
                self._emit(LOCK_ORDER_RULE, call,
                           f"untimed .wait() on {_dotted(recv)} while "
                           f"holding {held_desc} — only a Condition of "
                           f"the (sole) held lock may wait under it")
            return
        if meth == "communicate" and \
                not any(kw.arg == "timeout" for kw in call.keywords):
            self._emit(LOCK_ORDER_RULE, call,
                       f"subprocess communicate() while holding "
                       f"{held_desc}")
            return
        if meth == "block_until_ready" or dotted in _BLOCKING_DOTTED:
            self._emit(LOCK_ORDER_RULE, call,
                       f"device sync / process wait ({dotted or meth}) "
                       f"while holding {held_desc}")
            return
        if dotted == "time.sleep":
            arg = call.args[0] if call.args else None
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, (int, float))
                    and arg.value <= _SLEEP_UNDER_LOCK_THRESHOLD_S):
                self._emit(LOCK_ORDER_RULE, call,
                           f"time.sleep under {held_desc} stalls every "
                           f"thread contending for the lock")
            return
        # call resolution, one level deep -----------------------------
        if isinstance(recv, ast.Name) and recv.id == "self":
            summary = ctx["summaries"].get(meth)
            if summary:
                for key, kind in sorted(summary):
                    if key in held_keys:
                        if kind == "lock":
                            self._emit(LOCK_ORDER_RULE, call,
                                       f"call to self.{meth}() re-acquires "
                                       f"non-reentrant {key} already held "
                                       f"by this method")
                        continue
                    self._note_edge(held_keys[-1], key, call, ctx)
            return
        peer = self._resolve_peer(recv, ctx)
        if peer is None:
            return
        if meth in BLOCKING_METHODS.get(peer, ()):
            self._emit(LOCK_ORDER_RULE, call,
                       f"call to blocking {peer}.{meth}() while holding "
                       f"{held_desc}")
            return
        for key in LOCKING_METHODS.get(peer, {}).get(meth, ()):
            if key in held_keys:
                continue
            self._note_edge(held_keys[-1], key, call, ctx)

    @staticmethod
    def _wait_is_timed(call):
        if call.args:
            a = call.args[0]
            return not (isinstance(a, ast.Constant) and a.value is None)
        for kw in call.keywords:
            if kw.arg == "timeout":
                return not (isinstance(kw.value, ast.Constant)
                            and kw.value.value is None)
        return False

    def _wait_is_condition_of_held(self, recv, ctx):
        """Untimed Condition.wait is legal exactly when the condition's
        underlying lock is the ONLY lock held: the wait releases it, so
        nothing stays pinned while sleeping."""
        attr = _self_attr(recv)
        if attr is None or ctx["locks"].get(attr) != "condition":
            return False
        target = ctx["cond_target"].get(attr, attr)
        target_key = f"{ctx['cls']}.{target}"
        return {e["key"] for e in ctx["held"]} == {target_key}

    # -- rule 7: wire-contract ---------------------------------------------
    def check_wire_contract(self):
        """Collect this file's wire-contract facts (Replica interface,
        client relays + ops sent, server op table, error-registry
        imports, error-class shapes) onto ``self.wire_info`` for the
        cross-file parity pass, and run the per-file payload check:
        dict literals handed to the codec must be literal-keyed."""
        info = {"relpath": self.relpath,
                "pragmas": _parse_pragmas(self.source),
                "classes": [], "replica_methods": {}, "client_methods": {},
                "client_ops": {}, "server_ops": {}, "registry_imports": {},
                "replica_line": 1, "client_line": 1, "server_line": 1,
                "registry_line": 1}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ClassDef):
                self._collect_wire_class(node, info)
            elif isinstance(node, ast.FunctionDef) and \
                    node.name == "_error_registry" and \
                    self.relpath.endswith(_WIRE_ERRORS_FILE):
                info["registry_line"] = node.lineno
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Import):
                        for alias in sub.names:
                            info["registry_imports"].setdefault(
                                alias.name, sub.lineno)
                    elif isinstance(sub, ast.ImportFrom) and sub.module:
                        info["registry_imports"].setdefault(
                            sub.module, sub.lineno)
            elif isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute):
                    op = None
                    if f.attr == "_call" and node.args:
                        op = node.args[0]
                    elif f.attr == "_send" and len(node.args) >= 2:
                        op = node.args[1]
                    if isinstance(op, ast.Constant) and \
                            isinstance(op.value, str):
                        info["client_ops"].setdefault(op.value, node.lineno)
            if isinstance(node, ast.Compare) and \
                    isinstance(node.left, ast.Name) and \
                    node.left.id == "op" and len(node.ops) == 1 and \
                    isinstance(node.ops[0], ast.Eq) and \
                    isinstance(node.comparators[0], ast.Constant) and \
                    isinstance(node.comparators[0].value, str):
                info["server_ops"].setdefault(node.comparators[0].value,
                                              node.lineno)
        if self.relpath.endswith((_WIRE_CLIENT_FILE, _WIRE_SERVER_FILE)):
            self._check_wire_payloads()
        self.wire_info = info

    def _collect_wire_class(self, node, info):
        bases = [b for b in (_last(_dotted(b)) for b in node.bases) if b]
        init = next((m for m in node.body
                     if isinstance(m, ast.FunctionDef)
                     and m.name == "__init__"), None)
        ctor_ok = True
        if init is not None:
            a = init.args
            required = len(a.posonlyargs) + len(a.args) - len(a.defaults)
            accepts_msg = (len(a.posonlyargs) + len(a.args) >= 2) or \
                a.vararg is not None
            kw_required = any(d is None for d in a.kw_defaults)
            ctor_ok = accepts_msg and required <= 2 and not kw_required
        declared = set()
        for stmt in node.body:
            if isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        declared.add(t.id)
            elif isinstance(stmt, ast.AnnAssign) and \
                    isinstance(stmt.target, ast.Name):
                declared.add(stmt.target.id)
        info["classes"].append({
            "name": node.name, "bases": bases, "line": node.lineno,
            "has_reason": "reason" in declared,
            "has_retry": "retry_elsewhere" in declared,
            "ctor_ok": ctor_ok})
        methods = {m.name: m.lineno for m in node.body
                   if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
                   and not m.name.startswith("_")}
        if node.name == "Replica" and \
                self.relpath.endswith(_WIRE_REPLICA_FILE):
            info["replica_methods"] = methods
            info["replica_line"] = node.lineno
        elif node.name == "WireReplica" and \
                self.relpath.endswith(_WIRE_CLIENT_FILE):
            info["client_methods"] = methods
            info["client_line"] = node.lineno
        elif node.name == "ReplicaServer" and \
                self.relpath.endswith(_WIRE_SERVER_FILE):
            info["server_line"] = node.lineno

    def _check_wire_payloads(self):
        """Dict payloads handed to the codec (`write_frame`, `.send`,
        `._send`, `._safe_send`) must have literal string keys and no
        set values — non-literal keys defeat static parity checking and
        sets do not survive either wire format."""
        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            local_dicts = {}
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and \
                        len(node.targets) == 1 and \
                        isinstance(node.targets[0], ast.Name) and \
                        isinstance(node.value, ast.Dict):
                    local_dicts[node.targets[0].id] = node.value
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = _last(_dotted(node.func))
                if name not in _WIRE_SEND_FUNCS:
                    continue
                for arg in node.args:
                    d = arg if isinstance(arg, ast.Dict) else \
                        (local_dicts.get(arg.id)
                         if isinstance(arg, ast.Name) else None)
                    if d is None:
                        continue
                    for k in d.keys:
                        if k is None:
                            self._emit(WIRE_CONTRACT, node,
                                       "codec payload built with a **-"
                                       "expansion — wire payload dicts "
                                       "must be literal-keyed so the "
                                       "contract is statically checkable")
                        elif not (isinstance(k, ast.Constant)
                                  and isinstance(k.value, str)):
                            self._emit(WIRE_CONTRACT, k,
                                       "non-literal / non-string key in a "
                                       "codec payload dict — wire envelope "
                                       "keys must be string literals "
                                       "(msgpack/JSON both require it and "
                                       "static parity checks depend on it)")
                    for v in d.values:
                        for sub in ast.walk(v):
                            if isinstance(sub, (ast.Set, ast.SetComp)):
                                self._emit(WIRE_CONTRACT, sub,
                                           "set literal inside a codec "
                                           "payload — sets survive neither "
                                           "msgpack nor JSON; use a sorted "
                                           "list")

    # -- rule 8: replay-determinism ----------------------------------------
    def check_replay_determinism(self):
        entries = None
        for suffix, names in REPLAY_CRITICAL.items():
            if self.relpath.endswith(suffix):
                entries = names
                break
        if entries is None:
            return
        whole = "*" in entries

        def critical(fn):
            if whole:
                return True
            qn = self._qualname(fn)
            return any(qn == e or qn.startswith(e + ".") for e in entries)

        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not critical(fn):
                continue
            owner = self._owner_fn(fn)
            if owner is not None and critical(owner):
                continue  # nested def: walked with its owner
            self._check_replay_fn(fn)

    def _check_replay_fn(self, fn):
        set_names = self._settish_locals(fn)
        set_attrs = self._settish_class_attrs(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                self._check_replay_call(node, set_names, set_attrs)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                if self._is_settish(node.iter, set_names, set_attrs):
                    self._emit(REPLAY_DETERMINISM, node,
                               "iteration over an unordered set in a "
                               "REPLAY_CRITICAL scope — set order varies "
                               "across processes and feeds packing/replay "
                               "order; wrap in sorted(...)")
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                for gen in node.generators:
                    if self._is_settish(gen.iter, set_names, set_attrs):
                        self._emit(REPLAY_DETERMINISM, node,
                                   "comprehension over an unordered set in "
                                   "a REPLAY_CRITICAL scope — wrap the "
                                   "iterable in sorted(...)")

    def _check_replay_call(self, node, set_names, set_attrs):
        dotted = _dotted(node.func)
        name = _last(dotted)
        if dotted is not None:
            if dotted.startswith("random."):
                if not (name in _SEEDED_RNG_CTORS and node.args):
                    self._emit(REPLAY_DETERMINISM, node,
                               f"{dotted}() in a REPLAY_CRITICAL scope "
                               f"draws from process-local entropy — seed "
                               f"explicitly (random.Random(derive_seed(...))"
                               f") or thread the counter PRNG through")
                return
            if dotted.startswith(("np.random.", "numpy.random.")):
                if not (name in _SEEDED_RNG_CTORS and node.args):
                    self._emit(REPLAY_DETERMINISM, node,
                               f"module-level {dotted}() in a "
                               f"REPLAY_CRITICAL scope is unseeded global "
                               f"state — use a seeded np.random.default_rng"
                               f"(seed) / the counter PRNG")
                return
            if dotted == "os.urandom" or dotted.startswith("secrets.") or \
                    name in ("uuid1", "uuid4"):
                self._emit(REPLAY_DETERMINISM, node,
                           f"{dotted or name}() is OS entropy — a replay "
                           f"can never reproduce it; derive identity/seeds "
                           f"from (DS_SEED, request uid, position)")
                return
            if dotted in _REPLAY_WALL_CLOCK:
                if not self._clock_idiom_exempt(node):
                    self._emit(REPLAY_DETERMINISM, node,
                               f"{dotted}() outside a deadline/metrics "
                               f"idiom in a REPLAY_CRITICAL scope — wall "
                               f"clock flowing into token-visible state "
                               f"breaks bit-identical replay")
                return
        if isinstance(node.func, ast.Name) and node.func.id in ("id", "hash"):
            which = "id() is a process-local address" if \
                node.func.id == "id" else \
                "hash() is PYTHONHASHSEED-salted for str/bytes"
            self._emit(REPLAY_DETERMINISM, node,
                       f"{which} — keys/seeds derived from it differ "
                       f"across processes and replays; use derive_seed() "
                       f"or an explicit stable key")
            return
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "pop" and not node.args and \
                self._is_settish(node.func.value, set_names, set_attrs):
            self._emit(REPLAY_DETERMINISM, node,
                       "set.pop() removes an arbitrary element — "
                       "nondeterministic in a REPLAY_CRITICAL scope; pop "
                       "from a sorted/ordered structure")
            return
        if isinstance(node.func, ast.Name) and \
                node.func.id in ("list", "tuple", "enumerate", "iter") and \
                node.args and \
                self._is_settish(node.args[0], set_names, set_attrs):
            self._emit(REPLAY_DETERMINISM, node,
                       f"{node.func.id}() over an unordered set in a "
                       f"REPLAY_CRITICAL scope — materialized order varies "
                       f"across processes; use sorted(...)")

    def _clock_idiom_exempt(self, node):
        """Deadline/metrics idioms: the clock read participates in
        arithmetic/comparison (elapsed math, deadline checks) or is
        assigned to a deadline/metrics-named local."""
        p = self._parents.get(node)
        while p is not None and not isinstance(p, ast.stmt):
            if isinstance(p, (ast.BinOp, ast.Compare)):
                return True
            p = self._parents.get(p)
        if isinstance(p, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = p.targets if isinstance(p, ast.Assign) else [p.target]
            for t in targets:
                n = t.id if isinstance(t, ast.Name) else _self_attr(t)
                if n and any(h in n.lower() for h in _CLOCK_IDIOM_NAMES):
                    return True
        return False

    def _settish_locals(self, fn):
        out = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                v = node.value
                if isinstance(v, (ast.Set, ast.SetComp)) or (
                        isinstance(v, ast.Call)
                        and _last(_dotted(v.func)) in ("set", "frozenset")):
                    out.add(node.targets[0].id)
        return out

    def _settish_class_attrs(self, fn):
        """self-attributes assigned a set in the enclosing class's
        ``__init__`` — iterating them in a critical method is flagged."""
        cls = self._parents.get(fn)
        while cls is not None and not isinstance(cls, ast.ClassDef):
            cls = self._parents.get(cls)
        if cls is None:
            return set()
        init = next((m for m in cls.body if isinstance(m, ast.FunctionDef)
                     and m.name == "__init__"), None)
        if init is None:
            return set()
        out = set()
        for node in ast.walk(init):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                attr = _self_attr(node.targets[0])
                v = node.value
                if attr and (isinstance(v, (ast.Set, ast.SetComp)) or (
                        isinstance(v, ast.Call)
                        and _last(_dotted(v.func)) in ("set", "frozenset"))):
                    out.add(attr)
        return out

    def _is_settish(self, expr, set_names, set_attrs):
        if isinstance(expr, (ast.Set, ast.SetComp)):
            return True
        if isinstance(expr, ast.Call) and \
                _last(_dotted(expr.func)) in ("set", "frozenset"):
            return True
        if isinstance(expr, ast.Name):
            return expr.id in set_names
        attr = _self_attr(expr)
        if attr is not None:
            return attr in set_attrs
        if isinstance(expr, ast.BinOp) and \
                isinstance(expr.op, (ast.BitOr, ast.BitAnd, ast.Sub,
                                     ast.BitXor)):
            return self._is_settish(expr.left, set_names, set_attrs) or \
                self._is_settish(expr.right, set_names, set_attrs)
        return False

    # -- driver ------------------------------------------------------------
    def run(self, only=None):
        checks = {
            JIT_PURITY: self.check_jit_purity,
            HOST_SYNC: self.check_host_sync,
            THREAD_SHARED: self.check_thread_shared,
            SPEC_CONSISTENCY: self.check_spec_consistency,
            ENV_REGISTRY: self.check_env_registry,
            LOCK_ORDER_RULE: self.check_lock_order,
            WIRE_CONTRACT: self.check_wire_contract,
            REPLAY_DETERMINISM: self.check_replay_determinism,
        }
        for rule, check in checks.items():
            if only is None or rule in only:
                check()
        pragmas = _parse_pragmas(self.source)
        kept = []
        for v in self.violations:
            disabled = pragmas.get(v.line, ())
            if v.rule in disabled or "all" in disabled:
                continue
            kept.append(v)
        kept.sort(key=lambda v: (v.path, v.line, v.col, v.rule))
        # pragma'd edges leave the cycle graph too — a suppressed
        # acquisition site must not resurrect as a cycle report
        self.lock_edges = [
            e for e in self.lock_edges
            if LOCK_ORDER_RULE not in pragmas.get(e["line"], ())
            and "all" not in pragmas.get(e["line"], ())]
        return kept


def lock_cycle_violations(edges):
    """Cycle detection over merged acquisition edges. ``edges`` is a list
    of {src, dst, path, line, col, symbol} dicts; a DFS back-edge means
    two lock keys can be taken in both orders somewhere in the repo —
    each distinct cycle (deduped by its node set) is reported once,
    anchored at the back-edge acquisition site."""
    graph = {}
    sites = {}
    for e in edges:
        graph.setdefault(e["src"], set()).add(e["dst"])
        graph.setdefault(e["dst"], set())
        sites.setdefault((e["src"], e["dst"]), e)
    violations = []
    seen_cycles = set()
    color = {}  # node -> 1 (on stack) | 2 (done)
    stack = []

    def dfs(node):
        color[node] = 1
        stack.append(node)
        for nxt in sorted(graph[node]):
            if color.get(nxt) == 1:
                cycle = stack[stack.index(nxt):] + [nxt]
                key = frozenset(cycle)
                if key not in seen_cycles:
                    seen_cycles.add(key)
                    site = sites[(node, nxt)]
                    violations.append(Violation(
                        rule=LOCK_ORDER_RULE, path=site["path"],
                        line=site["line"], col=site["col"],
                        symbol=site["symbol"],
                        message=("lock-acquisition cycle "
                                 + " -> ".join(cycle)
                                 + " — two code paths take these locks "
                                   "in opposite orders; assign ranks in "
                                   "LOCK_ORDER and fix the inversion")))
            elif color.get(nxt) != 2:
                dfs(nxt)
        stack.pop()
        color[node] = 2

    for node in sorted(graph):
        if node not in color:
            dfs(node)
    return violations


def wire_contract_violations(infos):
    """Cross-file wire-contract parity over the merged per-file facts
    (``FileLinter.wire_info``). Each agreement is only checked when
    both sides were actually linted, so single-file invocations never
    report a "missing" counterpart they simply did not see:

    - every abstract ``Replica`` method needs a ``WireReplica`` relay,
      a client op send, and a ``ReplicaServer`` op-table entry;
    - every op the client sends must be dispatched by the server, and
      every server op must be reachable from a relay (else dead);
    - every module defining a ``ServingError`` subclass must appear in
      ``_error_registry()``'s lazy import list;
    - every ``ServingError`` subclass declares class-level ``reason`` /
      ``retry_elsewhere`` (itself or via a subtree ancestor) and stays
      constructible as ``cls(message)`` — what ``decode_error`` does.

    Violations honor inline pragmas of the file they anchor in."""
    replica = client = server = errors_info = None
    all_classes = []
    for info in infos:
        if info is None:
            continue
        rp = info["relpath"]
        if rp.endswith(_WIRE_REPLICA_FILE):
            replica = info
        if rp.endswith(_WIRE_CLIENT_FILE):
            client = info
        if rp.endswith(_WIRE_SERVER_FILE):
            server = info
        if rp.endswith(_WIRE_ERRORS_FILE):
            errors_info = info
        for c in info["classes"]:
            all_classes.append((info, c))
    out = []

    def emit(info, line, symbol, message):
        disabled = info["pragmas"].get(line, ())
        if WIRE_CONTRACT in disabled or "all" in disabled:
            return
        out.append(Violation(rule=WIRE_CONTRACT, path=info["relpath"],
                             line=line, col=0, symbol=symbol,
                             message=message))

    # ServingError subtree, transitive by base NAME across files
    subtree, known, changed = {}, {"ServingError"}, True
    while changed:
        changed = False
        for info, c in all_classes:
            if c["name"] in known:
                continue
            if any(b in known for b in c["bases"]):
                known.add(c["name"])
                subtree[c["name"]] = (info, c)
                changed = True

    def _inherits(c, field):
        seen = set()
        while True:
            if c[field]:
                return True
            parent = next((b for b in c["bases"] if b in subtree
                           and b not in seen), None)
            if parent is None:
                return False
            seen.add(parent)
            c = subtree[parent][1]

    for name in sorted(subtree):
        info, c = subtree[name]
        if not _inherits(c, "has_reason") or not _inherits(c, "has_retry"):
            emit(info, c["line"], name,
                 f"ServingError subclass {name} does not declare "
                 f"class-level reason/retry_elsewhere — the wire encodes "
                 f"both, and inheriting the base defaults makes the "
                 f"remote routing decision wrong or ambiguous")
        if not c["ctor_ok"]:
            emit(info, c["line"], name,
                 f"ServingError subclass {name} is not constructible as "
                 f"{name}(message) — decode_error() rebuilds it exactly "
                 f"that way, so extra required __init__ params break "
                 f"error decoding at the first remote failure")

    if errors_info is not None:
        imports = errors_info["registry_imports"]
        by_module = {}
        for name in sorted(subtree):
            info, _c = subtree[name]
            if info is errors_info:
                continue
            mod = info["relpath"]
            mod = mod[:-3] if mod.endswith(".py") else mod
            by_module.setdefault(mod.replace("/", "."), []).append(name)
        for mod, names in sorted(by_module.items()):
            if mod not in imports:
                emit(errors_info, errors_info["registry_line"], mod,
                     f"_error_registry() never imports {mod}, which "
                     f"defines ServingError subclass(es) "
                     f"{', '.join(sorted(names))} — until the module is "
                     f"imported those errors decode as WireProtocolError "
                     f"(wrong type, wrong retry semantics); add the "
                     f"import to the lazy list in wire/errors.py")

    if replica is not None and client is not None:
        for m in sorted(replica["replica_methods"]):
            if m not in client["client_methods"]:
                emit(client, client["client_line"], f"WireReplica.{m}",
                     f"abstract Replica method {m}() has no WireReplica "
                     f"relay — a remote fleet silently loses the method "
                     f"(AttributeError / base default instead of the "
                     f"worker's answer); add the relay in wire/client.py")
            elif m not in client["client_ops"]:
                emit(client, client["client_methods"][m],
                     f"WireReplica.{m}",
                     f"WireReplica.{m}() never sends wire op {m!r} — the "
                     f"relay exists but does not cross the process "
                     f"boundary")
    if client is not None and server is not None:
        for op in sorted(client["client_ops"]):
            if op not in server["server_ops"]:
                emit(server, server["server_line"], f"ReplicaServer.{op}",
                     f"client relays send wire op {op!r} but "
                     f"ReplicaServer._dispatch/_unary never handles it — "
                     f"that is a runtime WireProtocolError('unknown wire "
                     f"op') under traffic; add the op to the server table")
        for op in sorted(server["server_ops"]):
            if op in client["client_ops"] or op in _WIRE_HANDLE_OPS:
                continue
            if replica is not None and op in replica["replica_methods"]:
                continue
            emit(server, server["server_ops"][op], f"ReplicaServer.{op}",
                 f"server wire op {op!r} has no client relay — dead "
                 f"(untestable) dispatch arm; remove it or add the "
                 f"WireReplica relay")
    if replica is not None and server is not None:
        for m in sorted(replica["replica_methods"]):
            if m in server["server_ops"]:
                continue
            if client is not None and m in client["client_ops"]:
                continue  # reported via the client->server check above
            emit(server, server["server_line"], f"ReplicaServer.{m}",
                 f"abstract Replica method {m}() has no ReplicaServer op "
                 f"— adding a Replica method requires wiring BOTH the "
                 f"client relay and the server dispatch arm (see the "
                 f"checklist in docs/LINTING.md)")
    return out


def _lint_one(path, source, relpath, only=None):
    """→ (violations, linter) for one file, pragma-filtered. The
    returned linter carries cross-file state (lock edges, wire info)."""
    linter = FileLinter(path, source, relpath=relpath)
    return linter.run(only=only), linter


def lint_file(path, source=None, relpath=None, only=None):
    """All unsuppressed-by-pragma violations for one file, including a
    per-file lock-cycle pass (lint_paths instead runs one merged pass
    over every file so cross-file cycles surface) and the wire-contract
    parity pass over this file's facts alone."""
    if source is None:
        with open(path) as fd:
            source = fd.read()
    violations, linter = _lint_one(path, source, relpath, only=only)
    if only is None or LOCK_ORDER_RULE in only:
        violations = violations + lock_cycle_violations(linter.lock_edges)
    if only is None or WIRE_CONTRACT in only:
        violations = violations + wire_contract_violations(
            [linter.wire_info])
    violations.sort(key=lambda v: (v.path, v.line, v.col, v.rule))
    return violations


def _has_python_shebang(path):
    """Extensionless executable-script sniff: ``bin/ds_serve``-style
    entry points announce themselves with a ``#!...python`` first line
    and are held to every rule like any ``.py`` module."""
    try:
        with open(path, "rb") as fd:
            first = fd.readline(160)
    except OSError:
        return False
    return first.startswith(b"#!") and b"python" in first


def _iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(d for d in dirnames
                                     if d != "__pycache__")
                for fn in sorted(filenames):
                    full = os.path.join(dirpath, fn)
                    if fn.endswith(".py"):
                        yield full
                    elif "." not in fn and _has_python_shebang(full):
                        yield full


def count_host_sync_pragmas(paths):
    """Number of ``# ds-lint: disable=…host-sync…`` pragma SITES (one
    per source line carrying the comment) under ``paths`` — the counted
    budget ``bin/ds_lint --only=host-sync`` ratchets against: every
    pragma is one deliberate host sync, so the count growing means a
    new sync site slipped into a hot path. Counted from raw lines, not
    parsed suppressions, so the standalone-pragma next-line rule in
    :func:`_parse_pragmas` cannot double-count a site."""
    count = 0
    for path in _iter_py_files(paths):
        with open(path) as fd:
            for line in fd:
                idx = line.find("# ds-lint:")
                if idx < 0:
                    continue
                body = line[idx + len("# ds-lint:"):]
                body = body.split("--", 1)[0].strip()
                if not body.startswith("disable="):
                    continue
                rules = {r.strip()
                         for r in body[len("disable="):].split(",")}
                if HOST_SYNC in rules or "all" in rules:
                    count += 1
    return count


def lint_paths(paths, baseline=None, root=None, only=None):
    """Lint every .py file under ``paths``. → (violations, baselined)
    where ``baselined`` counts suppressions consumed from the baseline
    set of (rule, relpath, symbol) triples. Lock-acquisition edges are
    merged across ALL files before the single cycle pass — an inversion
    in kv_tier/ against an order established in serving/ is a cycle."""
    baseline = baseline or set()
    root = root or os.getcwd()
    violations, baselined = [], 0
    all_edges = []
    wire_infos = []
    for path in _iter_py_files(paths):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path) as fd:
            source = fd.read()
        file_violations, linter = _lint_one(path, source, rel, only=only)
        all_edges.extend(linter.lock_edges)
        wire_infos.append(linter.wire_info)
        for v in file_violations:
            if (v.rule, v.path, v.symbol) in baseline:
                baselined += 1
                continue
            violations.append(v)
    merged = []
    if only is None or LOCK_ORDER_RULE in only:
        merged.extend(lock_cycle_violations(all_edges))
    if only is None or WIRE_CONTRACT in only:
        merged.extend(wire_contract_violations(wire_infos))
    for v in merged:
        if (v.rule, v.path, v.symbol) in baseline:
            baselined += 1
            continue
        violations.append(v)
    return violations, baselined
