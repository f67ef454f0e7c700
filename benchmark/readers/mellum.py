"""Readers of ``mellum2-12b-moe8k-x4``'s own per-layer metrics, and the
functions of operations and bytes behind its rooflines. They count **the work
the mathematics needs**, from the cell's shapes and the step records' counts,
whatever implements it: a kernel that masks a block instead of skipping it, a
layout with padding rows, a forward run again for the backward all read low,
as they should, and none can pass 100 %.

Every reader returns ``None`` - and the line leaves the metric out - without
a traced run, without the cell's shapes in ``facts`` (a program from before
this configuration), or where the trace has no op of the kind it reads.
"""

import re

from benchmark.harness import trace as tr
from benchmark.harness.device import peaks_of

WINDOW_KERNELS = re.compile(r"^flash_window_(fwd|dkv|dq)")
FULL_KERNELS = re.compile(r"^flash_attention_(fwd|dkv|dq)")
EXPERT_KERNELS = re.compile(r"gmm_(ragged_dot|dw)|ragged[-_]dot")   # the weights' product is named transpose_jvp_gmm_dw__
EXCHANGE_SCOPE = "ds.moe_exchange"
FULL, SLIDING = "full_attention", "sliding_attention"


# ------------------------------------------------------------------ the work
def attention_pairs(seq_len, window=None):
    """(query, key) pairs one head of one sequence attends: a triangle, or a
    band of ``window`` keys."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_flops(pairs, heads, head_dim, backward=True):
    """Scores and the weighted sum forward (2 products of ``2 d`` operations a
    pair); the backward's four products (dV, dP, dQ, dK) beside them. The
    scores the backward kernels form again are recomputation: not counted."""
    return pairs * heads * head_dim * 2 * (2 + (4 if backward else 0))


def expert_flops(rows, hidden, width):
    """A held row through a gated expert: three products forward, and for
    each the backward's two (the rows' and the weights' gradients)."""
    return rows * 2 * hidden * width * 3 * 3


def expert_bytes(rows, hidden, width, held, itemsize=2):
    """The least a rank's grouped matmuls move a layer: its experts' three
    matrices read forward and again for the rows' gradient, their gradients
    written; a row's input, two hidden activations and output, read or
    written once forward and once more each in the backward."""
    weights = held * 3 * hidden * width * itemsize
    a_row = (2 * hidden + 2 * width) * itemsize
    return 3 * weights + 3 * rows * a_row


def forward_flops_per_token(shapes):
    """One token's forward through the cell's layers and head (matrix
    products and attention at the cell's sequence length; norms, the router's
    softmax and rotations left out)."""
    m, S = shapes["model"], shapes["seq_len"]
    D, H, G, d = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    per_layer = 2 * (D * H * d + 2 * D * G * d + H * d * D) + 2 * D * m["num_experts"] \
        + m["num_experts_per_tok"] * 3 * 2 * D * m["moe_intermediate_size"]
    total = 2 * D * m["vocab_size"]
    for kind in m["layer_types"]:
        pairs = attention_pairs(S, m["sliding_window"] if kind == SLIDING else None)
        total += per_layer + attention_flops(pairs, H, d, backward=False) / S
    return total


# ----------------------------------------------------------------- the trace
def _shapes(run):
    return (run.get("facts") or {}).get("moe8k_shapes")


def _whole_steps(trace):
    """→ {device: [(start, end)] of the step programs that ran whole inside
    the trace}: the modules at least half as long as the longest."""
    out = {}
    for device, events in tr.modules_of(trace).items():
        longest = max((dur for _, _, dur in events), default=0)
        out[device] = [(s, s + dur) for _, s, dur in events if dur >= 0.5 * longest > 0]
    return out


def step_seconds(trace, matches):
    """Mean over devices and whole steps of the seconds a step spends in the
    ops ``matches(name)`` accepts (their own time), and the steps counted."""
    steps = _whole_steps(trace)
    per_step = []
    for device, events in tr.ops_of(trace).items():
        spans = steps.get(device) or []
        if not spans:
            continue
        total = [0] * len(spans)
        for name, start, dur in events:
            if not matches(name):
                continue
            for i, (lo, hi) in enumerate(spans):
                if lo <= start < hi:
                    total[i] += dur
                    break
        per_step.extend(total)
    if not per_step:
        return None, 0
    return sum(per_step) / len(per_step) / 1e9, len(per_step)


def _step_time(trace):
    spans = [hi - lo for steps in _whole_steps(trace).values() for lo, hi in steps]
    return sum(spans) / len(spans) / 1e9 if spans else None


def _rows_a_rank(run):
    """Held rows a rank a step (all layers): the step records' ``n_expert_rows``
    over the chips."""
    records = [r["counts"] for r in (run["facts"].get("train_records") or []) if r.get("counts")]
    if not records:
        return None
    return records[-1]["n_expert_rows"] / _shapes(run)["chips"]


# ---------------------------------------------------------------- the readers
def train_mfu(run, spec):
    """3 x the forward operations of a token x the step's tokens, over the
    traced step's time at the chips' bf16 peak."""
    shapes = _shapes(run)
    if shapes is None or run.get("trace") is None:
        return None
    seconds = _step_time(run["trace"])
    if not seconds:
        return None
    tokens = shapes["sequences"] * shapes["seq_len"]
    peak = peaks_of(run["device"]["kind"])["bf16_tflops"] * 1e12 * shapes["chips"]
    flops = 3 * forward_flops_per_token(shapes) * tokens
    run["facts"]["train_mfu"] = {"forward_flops_per_token": forward_flops_per_token(shapes),
                                 "step_s": seconds, "step_flops": flops}
    return 100.0 * flops / seconds / peak


def attention_roofline(run, spec):
    """The metric's file names the kind of layer under ``attention``
    (``window`` | ``full``): the operations its band or triangle needs, a
    chip's share, over the time in that kind's three kernels, at the bf16 peak."""
    shapes = _shapes(run)
    if shapes is None or run.get("trace") is None:
        return None
    m = shapes["model"]
    window = spec["attention"] == "window"
    kernels = WINDOW_KERNELS if window else FULL_KERNELS
    seconds, steps = step_seconds(run["trace"], kernels.match)
    if not seconds:
        return None
    layers = sum(1 for k in m["layer_types"] if (k == SLIDING) == window)
    pairs = attention_pairs(shapes["seq_len"], m["sliding_window"] if window else None)
    flops = layers * shapes["sequences"] / shapes["chips"] \
        * attention_flops(pairs, m["num_attention_heads"], m["head_dim"])
    peak = peaks_of(run["device"]["kind"])["bf16_tflops"] * 1e12
    run["facts"].setdefault("attention_roofline", {})[spec["attention"]] = {
        "kernel_s_a_step": seconds, "steps": steps, "flops_a_chip_a_step": flops, "layers": layers}
    return 100.0 * flops / peak / seconds


def expert_matmul_roofline(run, spec):
    """The held rows' operations and the weights' and rows' bytes, forward and
    both backward products, over the time in the grouped matmul's kernels: the
    larger of operations over the bf16 peak and bytes over the HBM peak."""
    shapes = _shapes(run)
    if shapes is None or run.get("trace") is None:
        return None
    rows = _rows_a_rank(run)
    seconds, steps = step_seconds(run["trace"], EXPERT_KERNELS.search)
    if not rows or not seconds:
        return None
    m = shapes["model"]
    layers = len(m["layer_types"])
    peaks = peaks_of(run["device"]["kind"])
    flops = expert_flops(rows, m["hidden_size"], m["moe_intermediate_size"])
    moved = layers * expert_bytes(rows / layers, m["hidden_size"], m["moe_intermediate_size"],
                                  m["num_experts"] // shapes["chips"])
    least = max(flops / (peaks["bf16_tflops"] * 1e12), moved / (peaks["hbm_gbytes_per_s"] * 1e9))
    run["facts"]["expert_matmul"] = {
        "kernel_s_a_step": seconds, "steps": steps, "rows_a_rank_a_step": rows,
        "flops": flops, "bytes": moved,
        "bound": "flops" if flops / (peaks["bf16_tflops"] * 1e12) >= least else "bytes"}
    return 100.0 * least / seconds


def expert_rows_max_over_mean(run, spec):
    """The fullest rank's held picks in its fullest layer over the even
    share (a layer's picks over the ranks), from the last step record."""
    shapes = _shapes(run)
    records = [r["counts"] for r in ((run.get("facts") or {}).get("train_records") or [])
               if r.get("counts")]
    if shapes is None or not records:
        return None
    c = records[-1]
    mean = c["n_expert_rows"] / len(shapes["model"]["layer_types"]) / shapes["chips"]
    return c["expert_rows_max_rank"] / mean if mean else None


def moe_exchange_share(run, spec):
    """Time in the collectives the expert exchange issues (``ds.moe_exchange``:
    the gather of the axis's rows and the reduce-scatter of the summed picks,
    forward and backward) over device busy time. The ops are found by the
    scope in the profiler's own record of each op - its whole HLO text, whose
    metadata names the framework's op, or any of its statistics
    (``facts.moe_exchange`` lists them with their seconds); a trace that kept
    no such record gives nothing."""
    found = (run.get("facts") or {}).get("moe_exchange")
    if not found or run.get("trace") is None:
        return None
    busy = tr.busy_seconds(run["trace"])
    return 100.0 * found["seconds"] / busy if busy > 0 else None


def scoped_ops(path, scope=EXCHANGE_SCOPE):
    """The device ops of a kept ``.xplane.pb`` whose own record (any of an
    event's statistics: the framework's name of the op holds the
    ``jax.named_scope`` it was traced under) names ``scope`` → {"seconds":
    their time averaged over devices, "ops": {short name: seconds}, "bytes":
    {short name: the result's bytes, where the name carries a shape}}."""
    from jax.profiler import ProfileData
    per_device, ops = [], {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name.upper():
            continue
        total = 0
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            for event in line.events:
                if scope not in event.name and not any(scope in str(value)
                                                        for _, value in event.stats):
                    continue
                name = tr.short_name(event.name)
                total += event.duration_ns
                ops[name] = ops.get(name, 0) + event.duration_ns
        if total:
            per_device.append(total)
    if not per_device:
        return None
    n = len(per_device)
    return {"seconds": sum(per_device) / n / 1e9,
            "ops": {k: v / n / 1e9 for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:12]}}


_SHAPE = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|f8\w*)\[([\d,]*)\]")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1}


def largest_collectives(trace, top=6):
    """The collectives of a trace by the bytes of their result (from the
    shape in the op's name) → [[name, bytes, seconds], ...]."""
    seconds = tr.op_seconds(trace)
    out = []
    for name, s in seconds.items():
        if not tr.COLLECTIVE.search(name):
            continue
        size = 0
        for kind, dims in _SHAPE.findall(name):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            size = max(size, n * _ITEM.get(kind, 1))
        out.append([name, size, s])
    return sorted(out, key=lambda r: -r[1])[:top]
