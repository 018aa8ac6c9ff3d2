"""The program's one trace layer: host spans, compile records and device
scope names, on one clock and in one vocabulary.

A span is ``(name, start_ns, end_ns, id, parent, trial, pid, attrs)`` on
``time.time_ns()``, kept in a bounded ring in this process, always. While a
profiler trace runs, the same span is a ``jax.profiler.TraceAnnotation`` on
the ``/host:CPU`` plane, which counts from the file's ``profile_start_time``
on that same clock (measured on the v5e: the two agree to 0.01 ms), so
worker, executor, trial child and the child's device trace lie on one
axis. Importing this module never imports jax: spans annotate only where jax is already loaded, so the hunt
parent and the launchers stay off it. The ring is written at exit to
``<METAOPT_TPU_PROFILE_DIR>/<trial id or worker id>/spans.jsonl`` when that
variable is set (``hunt --profile-dir``), and never otherwise.

A device operation carries the scopes it was traced under in its
``op_name`` (``jit(train_step)/transpose(jvp(DecoderOnlyLM))/.../h0/attn/
attention/attention.core/...``). ``SCOPES`` closes over a train step's
source: every operation the program writes is under one of its
names, and three rules read an operation, all plain string work: of its
path :func:`layer_of` (which top-level scope owns it) and
:func:`direction` (forward, the forward's second run under remat, backward,
update); what carries no name of ``SCOPES`` the compiler made, and of its
HLO opcode :func:`compiler_kind` says what it is (a copy, a slice, a
loop's bookkeeping, a fusion under a nameless root).

The device side. A profiler trace's file holds the devices' operations
*and* the compiled programs that ran (utils/trace_device.py reads both by
field number; importing this module imports neither it nor jax). There a
fourth rule, ``trace_device.owner_of``, reads the step's own HLO for the
layer a nameless operation was made for: the one its users agree on, else
its operands' producers, else nobody.

``python -m metaopt_tpu.utils.trace DIR`` reads what a sweep left under DIR
(``hunt --profile-dir DIR``): the phases, the routes and, for a trial that
ran under ``client.profiled()``, its step by layer and direction, the
compiler's operations in it by kind and by owner and the ten largest;
``--scope NAME`` lists the operations under one scope instead.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import itertools
import json
import os
import re
import statistics
import sys
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

#: every host span there is, by the layer that records it
SPANS = (
    "worker.trial", "worker.reserve", "worker.report",
    "producer.observe", "producer.suggest",
    "executor.spawn", "executor.wait", "executor.collect",
    "trial.start", "trial.setup", "trial.data", "trial.init",
    "trial.restore", "trial.train", "trial.eval", "trial.save",
    "trial.report", "slice_and_shard_batch", "dispatch_step",
    "compile", "profiler.trace",
)
#: every device scope (``jax.named_scope``) of the train steps
SCOPES = ("embed", "attention", "attention.core", "ffn", "readout_xent",
          "optimizer", "eval",
          # an expert layer (models/moe.DroplessMoE): all of it, and its parts
          "moe", "moe.router", "moe.dispatch", "moe.experts", "moe.combine",
          # the shared experts beside the routed ones (DroplessMoE's
          # ``shared_d_ff``: a gated feed-forward every token meets)
          "moe.shared",
          # attention over selected keys (ops/sparse_index.py): the
          # indexer's projections and scores, and the exact top-k
          "attention.index", "attention.select",
          # a latent layer's own (models/lm_layers.LatentAttention): the K/V
          # down-projection, the latent's norm, the up-projection and the
          # shared key's rotary
          "attention.latent",
          # a linear-attention mixer (models/lm_layers.LinearAttention): all
          # of it, and the chunked scan of the gated delta rule
          # (ops/linear_attention.py), forward and backward
          "linear_attention", "linear_attention.core",
          # a state-space mixer (models/lm_layers.StateSpaceMixer): all of it,
          # and the selective scan (ops/selective_scan.py), both directions
          "ssm", "ssm.core",
          # a Mamba-2 mixer (models/lm_layers.ScalarDecayMixer): all of it (the
          # convolution and the gated group norm among it), and the chunked
          # scan of the scalar decay rule (ops/linear_attention.py), both
          # directions
          "ssd", "ssd.core",
          # a gated short convolution as a mixer
          # (models/lm_layers.ShortConvMixer): all of it, the two projections
          # among it, and its element-wise core (ops/short_conv.py: two gates
          # around three taps), both directions
          "short_conv", "short_conv.core",
          # a gated memory unit (models/lm_layers.GatedMemoryUnit): an earlier
          # layer's scan output gated by this layer's projection
          "gmu",
          # models/lm_layers.DifferentialAttention's own: lambda, a_1 -
          # lambda a_2, the pair's norm and (1 - lambda_init)
          "attention.diff",
          # a gate on attention's output (models/lm_layers.GroupedAttention
          # with ``spec.gate``): its projection, the activation and the
          # product a head, and their backward
          "attention.gate",
          # the trunk between the layers: the blocks' and the model's norms
          # outside a branch; the residual stream's sums and casts; what a
          # loss function does around the model and ``readout_xent`` (the
          # shifted rows, the mask, the masked mean, the step's counts)
          "norm", "residual", "loss")
#: the top-level scopes, a partition of a step's named operations: every
#: other scope is ``<layer>.<part>``, a part of its layer
LAYERS = tuple(s for s in SCOPES if "." not in s)
#: what :func:`direction` answers
DIRECTIONS = ("forward", "forward.again", "backward", "update")
#: what :func:`compiler_kind` answers: what an operation is that carries no
#: name of ``SCOPES``
COMPILER_KINDS = ("copy", "slice", "loop", "fusion", "other")
_KIND_OF = {
    **dict.fromkeys(("copy", "copy-start", "copy-done", "transpose",
                     "reshape", "convert"), "copy"),
    **dict.fromkeys(("slice", "slice-start", "slice-done", "dynamic-slice",
                     "dynamic-update-slice", "concatenate", "pad"), "slice"),
    **dict.fromkeys(("while", "conditional", "call", "tuple",
                     "get-tuple-element"), "loop"),
    "fusion": "fusion",
}
#: spans a train loop makes every step. The ring keeps one whole only if it
#: has a child (the step that compiled); the others are summed into the
#: enclosing span's ``attrs["per_step"]`` as ``{name: [count, seconds]}``, so a
#: trial of thousands of steps does not push its own phases out of the ring
PER_STEP = ("slice_and_shard_batch", "dispatch_step")
PROFILE_DIR_ENV = "METAOPT_TPU_PROFILE_DIR"
RING = 8192

_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_local = threading.local()  # .stack: open span ids; .compile: pending phases
_info = json.loads(os.environ.get("METAOPT_TPU_TRIAL_INFO") or "{}")
#: what a trial child inherits from its executor: its id, and the span that
#: covers its life there (``executor.wait``) as the parent of its top-level
#: spans
_trial: Optional[str] = _info.get("id")
_root: Optional[str] = _info.get("under")
_owner: Optional[str] = _trial
_dump_dir: Optional[str] = None
_watching = False


def new_id() -> str:
    return f"{os.getpid():x}.{next(_ids):x}"


def _new(name: str, id: Optional[str], trial: Optional[str],
         attrs: Dict[str, Any]) -> dict:
    """A span under the innermost open one of this thread, not yet timed."""
    assert name in SPANS, name
    stack = getattr(_local, "stack", None)
    over = stack[-1] if stack else {"id": _root, "trial": _trial}
    return {"name": name, "start_ns": None, "end_ns": None,
            "id": id or new_id(), "parent": over["id"],
            "trial": trial or over["trial"], "pid": os.getpid(),
            "attrs": attrs}


def record(name: str, start_ns: int, end_ns: int, *, id: Optional[str] = None,
           parent: Optional[str] = None, trial: Optional[str] = None,
           **attrs: Any) -> dict:
    """One finished span into the ring."""
    rec = _new(name, id, trial, attrs)
    rec.update(start_ns=int(start_ns), end_ns=int(end_ns),
               parent=parent or rec["parent"])
    _ring.append(rec)
    return rec


@contextlib.contextmanager
def span(name: str, *, id: Optional[str] = None, trial: Optional[str] = None,
         **attrs: Any) -> Iterator[dict]:
    """Time the block as span ``name``; yields the record, whose times are
    set and which joins the ring when the block ends (one of ``PER_STEP``
    without a child is summed into the span around it instead)."""
    rec = _new(name, id, trial, attrs)
    jax = sys.modules.get("jax")
    note = contextlib.nullcontext()
    if jax is not None and hasattr(jax, "profiler"):
        watch_compiles()
        note = jax.profiler.TraceAnnotation(name)
    stack = _local.__dict__.setdefault("stack", [])
    stack.append(rec)
    rec["start_ns"] = time.time_ns()
    try:
        with note:
            yield rec
    finally:
        rec["end_ns"] = time.time_ns()
        stack.pop()
        if name in PER_STEP and stack and not (
                _ring and _ring[-1]["parent"] == rec["id"]):
            summed = stack[-1]["attrs"].setdefault("per_step", {}).setdefault(
                name, [0, 0.0])
            summed[0] += 1
            summed[1] += seconds(rec)
        else:
            _ring.append(rec)


def seconds(rec: dict) -> float:
    return (rec["end_ns"] - rec["start_ns"]) * 1e-9


def scope(name: str):
    """``jax.named_scope(name)`` for a name of ``SCOPES``: a context manager
    or a decorator; the name shows in every traced op's ``op_name``."""
    assert name in SCOPES, name
    import jax

    return jax.named_scope(name)


def layer_of(op_name: str) -> Optional[str]:
    """The layer that owns a device operation: the top-level scope of the
    OUTERMOST name of ``SCOPES`` on its path, bare or inside a transform's
    brackets (``transpose(jvp(readout_xent))``). ``attention.core`` is
    ``attention``'s part, not a layer; a q/k norm inside ``attention`` is
    under ``norm`` too and stays attention's: the outermost name owns the
    path, the one tie-break. None: the path carries no name of the
    program's, so the compiler made the operation (a copy, a slice).

    XLA fuses across scopes and a fusion carries its root's ``op_name``:
    the answer is for the operation *named* so, not for every source line
    folded into it."""
    for part in re.split(r"[/()]", op_name):
        if part in SCOPES:
            return part.partition(".")[0]
    return None


def direction(op_name: str) -> str:
    """One of ``DIRECTIONS`` for a device operation of a train step:
    ``forward.again`` where jax says the operation is a rematerialised
    block's second run (``rematted_computation`` on the path, which lies
    under ``transpose(`` too), else ``backward`` under ``transpose(`` (a
    ``custom_vjp`` kernel's backward rule is traced there), else ``update``
    under the scope ``optimizer``, else ``forward``.

    The same limit as :func:`layer_of`'s: a second-run product that XLA
    folds into a backward fusion reads ``backward``, an update folded into
    a weight-gradient matmul likewise."""
    if "rematted_computation" in op_name:
        return "forward.again"
    if "transpose(" in op_name:
        return "backward"
    return "update" if layer_of(op_name) == "optimizer" else "forward"


def compiler_kind(opcode: str) -> str:
    """One of ``COMPILER_KINDS`` for the HLO opcode of an operation that has
    no layer (``layer_of`` is None: the compiler made it). The table, settled
    on the opcodes of PR 51's first traced runs on the v5e:

    - ``copy``, a pass that writes a whole array again: as it is (``copy``,
      the asynchronous pair ``copy-start`` / ``copy-done``), in another
      order (``transpose``, a ``reshape`` that is not a bitcast) or in
      another type (``convert``: the float32 experts read as bfloat16);
    - ``slice``, a move of a part: ``slice``, ``dynamic-slice``,
      ``dynamic-update-slice``, ``concatenate``, ``pad`` (the asynchronous
      ``slice-start`` / ``slice-done`` are ``async-start`` / ``async-done``
      around a ``slice``: ``trace_device.opcode_of`` looks inside);
    - ``loop``, control and its bookkeeping: ``while``, ``conditional``,
      ``call``, ``tuple``, ``get-tuple-element`` (a loop's counters run
      inside fusions of their own and read ``fusion``);
    - ``fusion``, a fusion whose root carries no name of ``SCOPES``;
    - ``other``, the rest (a ``broadcast`` that fills a buffer, ``iota``,
      a ``custom-call`` without a name): the closed list's guard, under 5 %
      of the nameless time in the recorded steps.

    An opcode is taken as the compiler spells it, or from the front of an
    instruction's name where a file holds no program (``copy-done.14``,
    ``slice-start.3``, a renamed ``bitcast_fusion.3``).

    The same limit as :func:`layer_of`'s: a fusion is whatever its root
    is, so a fused copy under a nameless root reads ``fusion``."""
    opcode = opcode.lstrip("%")
    kind = _KIND_OF.get(opcode) or _KIND_OF.get(opcode.partition(".")[0])
    if kind is None and "fusion" in opcode:
        kind = "fusion"  # a renamed fusion: ``bitcast_fusion.3``
    return kind or "other"


def spans(name: Optional[str] = None) -> List[dict]:
    return [s for s in list(_ring) if name is None or s["name"] == name]


def owner(worker_id: str) -> None:
    """Name this process's dump after ``worker_id`` (a trial's id wins)."""
    global _owner
    _owner = _owner or worker_id


def spawn_info() -> dict:
    """What rides to a child in ``METAOPT_TPU_TRIAL_INFO`` from under an
    ``executor.spawn`` span: that span's id and the stamp, from which the
    child closes ``trial.start`` at its import, and the id ``under`` which
    the executor will record the child's life (``executor.wait``), the
    parent of the child's other top-level spans."""
    stack = getattr(_local, "stack", None)
    return {"span": stack[-1]["id"] if stack else None, "under": new_id(),
            "spawn_ns": time.time_ns()}


# -- compile records --------------------------------------------------------

_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
           "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
           "/jax/core/compile/backend_compile_duration": "backend_s"}


def _fn(fun_name: Any) -> str:
    """``train_step`` from jax 0.9.0's ``jit(train_step)``, ``jit_train_step``."""
    name = str(fun_name or "")
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name.removeprefix("jit_")


def _heard_duration(event: str, duration_secs: float, **kw: Any) -> None:
    """Phases of this thread's compile requests, kept by function name until
    the backend phase closes one: inner jits are traced on the way and
    ``eval_shape`` traces what never compiles, so only the name tells which
    trace and lowering were the request's own."""
    phase = _PHASES.get(event)
    if phase is None:
        return
    now = time.time_ns()
    fn = _fn(kw.get("fun_name"))
    pending = _local.__dict__.setdefault("compile", {})
    mine = pending.setdefault(fn, {})
    # heard twice before the close (eval_shape traces, then jit finds that
    # trace again): the earlier start, both durations
    start, secs = mine.get(phase, (now, 0.0))
    mine[phase] = (min(start, now - int(duration_secs * 1e9)),
                   round(secs + duration_secs, 6))
    if phase == "backend_s":
        _local.compile = {}
        mine = {**pending.get("", {}), **pending[fn]}  # retrieval is unnamed
        phases = {k: v for k, v in mine.items() if k != "hit"}
        record("compile", min(s for s, _ in phases.values()), now, fn=fn,
               cache_hit="hit" in mine,
               **{k: d for k, (_, d) in phases.items()})


def _heard_event(event: str, **_: Any) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _local.__dict__.setdefault("compile", {}).setdefault(
            "", {})["hit"] = True


def watch_compiles() -> None:
    """Register the one ``jax.monitoring`` listener (needs jax; idempotent)."""
    global _watching
    if not _watching:
        _watching = True
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_heard_duration)
        monitoring.register_event_listener(_heard_event)


@contextlib.contextmanager
def profile(trace_dir: str) -> Iterator[None]:
    """A ``jax.profiler`` trace of the block into ``trace_dir``, inside a
    span ``profiler.trace``. The planes of the file count from its
    ``profile_start_time`` (``Task Environment`` plane; nanoseconds on this
    same wall clock), which falls inside ``start_trace``."""
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    with span("profiler.trace", dir=trace_dir):
        jax.profiler.start_trace(trace_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()


# -- the dump, and its reader -----------------------------------------------

def dump(path: str) -> None:
    """The ring as JSON lines."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        for rec in spans():
            f.write(json.dumps(rec, default=str) + "\n")


def trial_dir(base: str) -> str:
    return os.path.join(base, str(_owner or f"pid{os.getpid()}"))


def dump_under(base: Optional[str]) -> None:
    """Dump at exit to ``<base>/<trial or worker id>/spans.jsonl``."""
    global _dump_dir
    if base and _dump_dir is None:
        _dump_dir = base

        def _at_exit():
            try:
                dump(os.path.join(trial_dir(base), "spans.jsonl"))
            except OSError:
                pass  # the directory went away: nothing to tell anyone

        atexit.register(_at_exit)


def load(base: str) -> List[dict]:
    """Every span dumped under ``base``. A full ring has lost its process's
    earliest spans, whose time then reads as their parent's own: said on
    stderr."""
    out = []
    for root, _, files in os.walk(base):
        if "spans.jsonl" in files:
            with open(os.path.join(root, "spans.jsonl")) as f:
                recs = list(map(json.loads, f))
            if len(recs) >= RING:
                print(f"warning: {root}/spans.jsonl is a full ring ({RING}): "
                      "its earliest spans are lost", file=sys.stderr)
            out += recs
    return out


def self_ns(rec: dict, by_parent: Dict[str, List[dict]]) -> int:
    """A span's time less what its children cover of it (their union)."""
    at, end, covered = rec["start_ns"], rec["end_ns"], 0
    for s, e in sorted((c["start_ns"], min(c["end_ns"], end))
                       for c in by_parent.get(rec["id"], ())):
        if e > at:
            covered += e - max(s, at)
            at = e
    return end - rec["start_ns"] - covered


def table(recs: List[dict]) -> List[dict]:
    """Per phase over the trials: median and largest seconds, self time,
    share of ``worker.trial``; and the gap between one ``worker.trial`` and
    the next of the same worker process as ``(hand-off)``."""
    by_parent: Dict[str, List[dict]] = collections.defaultdict(list)
    for r in recs:
        by_parent[r["parent"]].append(r)
    per: Dict[str, Dict[Any, List[float]]] = collections.defaultdict(
        lambda: collections.defaultdict(lambda: [0.0, 0.0]))
    for r in recs:
        own = self_ns(r, by_parent) * 1e-9
        for name, (_, secs) in r["attrs"].get("per_step", {}).items():
            step = per[name][r["trial"]]  # the steps summed at the source
            step[0] += secs
            step[1] += secs
            own -= secs
        cell = per[r["name"]][r["trial"]]
        cell[0] += seconds(r)
        cell[1] += own
    roots = sorted((r for r in recs if r["name"] == "worker.trial"),
                   key=lambda r: (r["pid"], r["start_ns"]))
    for a, b in zip(roots, roots[1:]):
        if a["pid"] == b["pid"]:
            gap = (b["start_ns"] - a["end_ns"]) * 1e-9
            per["(hand-off)"][b["trial"]] = [gap, gap]
    whole = statistics.median(
        [v[0] for v in per.get("worker.trial", {}).values()] or [0.0])
    rows = []
    for name, cells in per.items():
        tot, own = zip(*cells.values())
        rows.append({"phase": name, "trials": len(cells),
                     "median_s": statistics.median(tot), "max_s": max(tot),
                     "self_median_s": statistics.median(own),
                     "share": statistics.median(own) / whole if whole else None})
    return sorted(rows, key=lambda r: -r["self_median_s"])


def main(argv: List[str]) -> int:
    """``DIR [--scope NAME]``: the phase table and the routes of what a sweep
    left under DIR and, a device trace it holds (a trial that ran under
    ``client.profiled()``), that trial's step by layer and direction and the
    compiler's operations in it; with ``--scope``, the operations under that
    one scope."""
    scope = argv[argv.index("--scope") + 1] if "--scope" in argv else None
    if scope is not None and scope not in SCOPES:
        print(f"no scope {scope!r}: one of {', '.join(SCOPES)}",
              file=sys.stderr)
        return 2
    recs = load(argv[0])
    print(f"{len(recs)} spans under {argv[0]}; self = a span less its "
          "children; share = median self / median worker.trial")
    print(f"{'phase':<24}{'trials':>7}{'median s':>10}{'max s':>10}"
          f"{'self s':>10}{'share':>8}")
    for r in table(recs):
        share = "" if r["share"] is None else f"{100 * r['share']:.1f}%"
        print(f"{r['phase']:<24}{r['trials']:>7}{r['median_s']:>10.3f}"
              f"{r['max_s']:>10.3f}{r['self_median_s']:>10.3f}{share:>8}")
    print_routes(recs)
    print_device(recs, argv[0], scope)
    return 0


def print_device(recs: List[dict], base: str, scope: Optional[str]) -> None:
    """The device side of every ``profiler.trace`` span under ``base``
    (utils/trace_device.py reads the file; a directory that was moved is
    looked for under ``base`` by its last part, the trial's id)."""
    marks = [r for r in recs if r["name"] == "profiler.trace"]
    if not marks:
        return
    from metaopt_tpu.utils import trace_device

    for r in marks:
        said = str(r["attrs"].get("dir", ""))
        where = said if os.path.isdir(said) else os.path.join(
            base, os.path.basename(said.rstrip("/")))
        loaded = trace_device.load(where)
        if loaded is None or not loaded.programs and not loaded.ops:
            print(f"trial {r['trial']}: no device trace under {where}")
            continue
        print(f"trial {r['trial']}: device trace {loaded.path}")
        if scope is None:
            trace_device.print_step(trace_device.step_table(loaded))
        else:
            trace_device.print_scope(trace_device.scope_table(loaded, scope))


def _scores_form(said: dict) -> str:
    """``ops/sparse_index.py::index_scores_route``'s answer in words."""
    if said["route"] != "pallas":
        return said["route"]
    tq, tk = said["tiles"]
    return f"pallas, tiles {tq} x {tk}, passes {said['depth']} deep"


def _kernels_form(said: Optional[dict]) -> str:
    """``ops/window_attention.py::window_kernels``'s answer in words ("" for
    a layer without a window, or a trace from before it was said)."""
    if not said:
        return ""
    form = (f"one slab of keys a sub-tile of {said['sub']}"
            if said["kernels"] == "slab" else "a walk over the tiles seen")
    return (f", kernels {form} in tiles of {said['tile']}, "
            f"{said['walked_over_seen']:g} keys read a key seen")


#: ``ops/latent_attention.py::hand_over``'s answer in words, and
#: ``ops/grouped_hand_over.py::hand_over``'s (a trace from before it was
#: said has neither); under ``("ssd", answer)``
#: ``ops/ssd_hand_over.py::hand_over``'s for the Mamba-2 layers, under
#: ``("linear", answer)`` ``ops/delta_hand_over.py::hand_over``'s
_HAND_OVER = {
    None: "", "passes": "",
    "one pass": ", q and k from their products in one pass a direction",
    "copies": "",
    "in place": (", the kernels reading q after one pass, K, V and out "
                 "where the matmuls leave them, the shared key joined in "
                 "VMEM"),
    ("ssd", "one pass"): (", the scan's operands from the products and the "
                          "gated norm from its output in one pass a "
                          "direction"),
    ("linear", "one pass"): (", q, k and v from their products heads first "
                             "and the gated norm from the scan's output in "
                             "one pass a direction"),
}


def _runs(numbers) -> str:
    """[0, 1, 2, 4] -> "0-2, 4": sorted numbers as runs."""
    runs = []
    for n in sorted(numbers):
        if runs and n == runs[-1][1] + 1:
            runs[-1][1] = n
        else:
            runs.append([n, n])
    return ", ".join(str(a) if a == b else f"{a}-{b}" for a, b in runs)


def _room(remat: dict) -> str:
    """What ``attrs["remat"]`` says of the matrix products a block could
    keep (models/lm_remat.py::remat_keeps): the bytes kept over all the layers
    and the room they were held against and, of the names the rule
    declined, the bytes that would have stood with them; "" for a model
    that has no such candidates."""
    sizes = remat.get("bytes")
    if not sizes:
        return ""
    gb = lambda names: (  # noqa: E731
        f"{sum(sizes[n] for n in names) / 1e9:.2f} GB")
    room = ("no room stated by the device" if remat["room"] is None
            else f"room {remat['room'] / 1e9:.2f} GB")
    kept = [n for n in sizes if n in remat["keeps"]]
    out = f"; kept bytes {gb(kept)} of {room}" if kept else ""
    if len(kept) < len(sizes):
        out += (f"; {', '.join(n for n in sizes if n not in kept)}: kept "
                f"bytes {gb(sizes)} of {room}: not kept")
    return out


def print_routes(recs: List[dict]) -> None:
    """A line a trial: which attention route its steps took, as its
    ``trial.setup`` span has it (ops/attention.attention_route, the one
    rule, asked for the training and the evaluation rate); for a
    model with a layer pattern the embedding's table and its gradient's
    route (ops/embed.embed_gradient_route), a line each kind of layer (a
    state-space kind's route, chunk and state type, which layers hand on and which
    read), what the expert layers hold, and (from ``trial.train``) what they counted and, where
    layers select their keys, the selected pairs among the causal ones; for
    a rematerialised model what a block keeps besides its input and, of
    the matrix products it could keep, the bytes against the device's
    room."""
    held = {}  # trial -> its setup's attrs["moe"]
    for r in recs:
        attrs = r["attrs"]
        route = attrs.get("attention") if r["name"] == "trial.setup" else None
        if route:
            print(f"trial {r['trial']}: attention {route['train']} in "
                  f"training (dropout {route['dropout']}), {route['eval']} "
                  "in evaluation")
            for kind, how in attrs.get("attention_layers", {}).items():
                if kind == "linear":
                    print(f"trial {r['trial']}: linear layers "
                          f"{_runs(how['layers'])}: gated delta rule, "
                          f"{how['heads'][0]} of {how['heads'][1]} heads, "
                          f"keys {how['key_dim']}, values "
                          f"{how['value_dim']}, convolutions of "
                          f"{how['conv']}, chunks of {how['chunk']} by "
                          f"{how['route']}" + _HAND_OVER.get(
                              ("linear", how.get("hand_over")), ""))
                    continue
                if kind == "ssm":
                    print(f"trial {r['trial']}: state-space layers "
                          f"{_runs(how['layers'])}: selective scan over "
                          f"{how['d_inner']} channels x {how['d_state']} "
                          f"states ({how['state']}), convolutions of "
                          f"{how['conv']}, steps of rank {how['dt_rank']}, "
                          f"chunks of {how['chunk']} by {how['route']}" + (
                              f"; layer {how['hands_on']} hands on its scan "
                              "output" if how["hands_on"] else ""))
                    continue
                if kind == "ssd":
                    print(f"trial {r['trial']}: Mamba-2 layers "
                          f"{_runs(how['layers'])}: scalar decay rule, "
                          f"{how['heads']} heads of {how['head_dim']} on "
                          f"{how['groups']} groups' B and C, state "
                          f"{how['state']}, convolution of {how['conv']} "
                          f"with a bias, norm gated over groups of "
                          f"{how['norm_group']}, chunks of {how['chunk']} by "
                          f"{how['route']}, a program a "
                          f"{how['program']}" + _HAND_OVER.get(
                              ("ssd", how.get("hand_over")), ""))
                    continue
                if kind == "short-conv":
                    print(f"trial {r['trial']}: gated short convolutions, "
                          f"layers {_runs(how['layers'])}: "
                          f"{how['channels']} channels, {how['taps']} taps "
                          f"between two gates, the core by {how['route']}")
                    continue
                if kind == "gmu":
                    print(f"trial {r['trial']}: gated memory units "
                          f"{_runs(how['layers'])}: {how['d_inner']} wide, "
                          f"reading layer {how['reads']}'s scan output")
                    continue
                if kind.startswith("latent"):
                    print(f"trial {r['trial']}: layers "
                          f"{_runs(how['layers'])}: latent attention, "
                          f"{how['heads']} heads, q\u00b7k {how['nope']} + "
                          f"{how['rope']} rotary on one shared key, v "
                          f"{how['v']}, K/V rank {how['rank']}, by "
                          f"{how['route']}" + _HAND_OVER[how["hand_over"]])
                    continue
                scores = how.get("index_scores")
                pairs = how.get("differential")
                print(f"trial {r['trial']}: {kind} layers: {how['route']}, "
                      f"mask by {how['mask']}" + (
                          f", index scores by {_scores_form(scores)}"
                          if scores else "") + _kernels_form(
                              how.get("kernels")) + _HAND_OVER[
                                  how.get("hand_over")] + (
                          f"; layers {_runs(how['layers'])} differential: "
                          f"{pairs[0]} query pairs on {pairs[1]} K/V pairs, "
                          f"q\u00b7k {pairs[2]}, v {pairs[3]}" + (
                              f", reading layer {how['reads']}'s K and V"
                              if "reads" in how else "")
                          if pairs else "") + (
                          f"; layers {_runs(how['layers'])}: {how['heads']} "
                          f"query heads on {how['kv_heads']} K/V heads, "
                          f"rotary {how['rotary']}, gate {how['gate']}"
                          if "rotary" in how else ""))
            embed = attrs.get("embed")
            if embed:
                print(f"trial {r['trial']}: embedding: {embed['rows']} rows "
                      f"x {embed['width']}"
                      + (", the head's table too" if embed["tied"] else "")
                      + f", {embed['tokens']} tokens a step, gradient by "
                      f"{embed['gradient']}")
            remat = attrs.get("remat")
            if remat:
                print(f"trial {r['trial']}: remat: {remat['blocks']} blocks "
                      f"keep {', '.join(remat['keeps'])}"
                      + _room(remat))
            moe = attrs.get("moe")
            if moe:
                held[r["trial"]] = moe
                first, count = moe["held"]
                how = moe.get("experts")
                print(f"trial {r['trial']}: experts {first}-"
                      f"{first + count - 1} of {moe['routed_over']} held, "
                      f"top {moe['top_k']}, products by {moe['products']}"
                      + (f", gate and up as {how['gate_up']}, gating by "
                         f"{how['gating']}" if how else "")
                      + (f", width {how['width']}" if how
                         and "width" in how else ""))
                if "scoring" in moe:
                    bias = " on score + bias" if moe["bias"] else ""
                    print(f"trial {r['trial']}: experts: "
                          f"{moe['scoring']} scores, "
                          f"{moe['top_k']} of {moe['routed_over']}{bias}, "
                          f"weights scaled {moe['scale']:g}, {count} held, "
                          f"shared as one of {moe['shared_d_ff']}" + (
                              f"; layers {_runs(range(moe['dense_layers']))}"
                              f" dense {moe['d_ff']}"
                              if moe["dense_layers"] else "") + (
                              f"; layers {_runs(moe['layers'])}: two "
                              "matrices an expert, no gate"
                              if not moe.get("gated", True) else ""))
        counts = attrs.get("moe") if r["name"] == "trial.train" else None
        if counts:
            # the counts are the routed layers': behind the leading dense ones
            # (or where the setup names them, e.g. one sublayer a block)
            dense = held.get(r["trial"], {}).get("dense_layers", 0)
            numbers = held.get(r["trial"], {}).get("layers") or range(
                dense, dense + len(counts["items"]))
            for i, (layer, items) in enumerate(zip(numbers,
                                                   counts["items"])):
                mean = sum(items) / len(items)
                print(f"trial {r['trial']}: layer {layer}: {sum(items)} "
                      f"items to held experts, fullest "
                      f"{max(items) / mean if mean else 0.0:.2f}x the mean, "
                      f"{counts['dropped'][i]} dropped")
            said = held.get(r["trial"], {})
            rows = (attrs.get("steps", 0) * len(counts["items"])
                    * said.get("buffer_rows", 0))
            if rows and "chunks" in counts:
                moved = sum(counts["chunks"]) * said["chunk_rows"]
                filled = sum(map(sum, counts["items"]))
                print(f"trial {r['trial']}: routing moved "
                      f"{100 * moved / rows:.1f} % of the buffers' rows, "
                      f"the experts' passes {100 * filled / rows:.1f} %")
        pairs = (attrs.get("selection") or {}) \
            if r["name"] == "trial.train" else {}
        for layer, (chosen, seen) in enumerate(zip(
                pairs.get("selected_pairs", ()),
                pairs.get("causal_pairs", ()))):
            print(f"trial {r['trial']}: layer {layer}: attention over "
                  f"{chosen} selected of {seen} causal pairs, "
                  f"{100 * chosen / seen if seen else 0.0:.1f} %")


dump_under(os.environ.get(PROFILE_DIR_ENV))
if "spawn_ns" in _info:  # a trial child: from the executor's Popen to here
    record("trial.start", _info["spawn_ns"], time.time_ns(),
           parent=_info.get("span"))

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
