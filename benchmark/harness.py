"""The shared harness: finds a cell's files by the names in
BENCHMARK.json, boots the node in this process, hands a `Cell` to the
traffic generator, and turns what the window left (request log, spans,
counters, trace) into the one result line.  It names no cell, no
configuration and no metric: those are files (see README.md).
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from benchmark import stats

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
SEC = 10**9
MINUTE = 60 * SEC
BLOCK = 2 * 3600 * SEC


def say(tag: str, **kw) -> None:
    print(json.dumps({tag: kw}, default=str), flush=True)


def load_json(*parts) -> dict:
    with open(HERE.joinpath(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(bench, cell, configuration, traffic) for a workload's name."""
    with open(REPO / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["name"] == name]
    if not cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[0]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(REPO / cfg_entry["file"]) as f:
        cfg = json.load(f)
    return bench, cell, cfg, load_json("traffic", cell["traffic"] + ".json")


def metrics_of(bench: dict, cell_name: str, group: str) -> list[dict]:
    """The metrics of a group that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def data_start(now_nanos: int) -> int:
    """Start of the latest 2 h block that began at least 75 minutes ago:
    nothing a run writes (at most an hour and a few minutes of data)
    lies in the future or reaches the block's end, so nothing seals."""
    return (now_nanos - 75 * MINUTE) // BLOCK * BLOCK


# ---------------------------------------------------------------------------
# node
# ---------------------------------------------------------------------------


def build_ruleset(rules: dict | None):
    """The configuration's rules as the program's RuleSet (built as
    chip_smoke.smoke_ruleset builds its)."""
    if not rules:
        return None
    from m3_tpu.metrics.aggregation import AggregationID, AggregationType
    from m3_tpu.metrics.filters import TagsFilter
    from m3_tpu.metrics.pipeline import AggregationOp, Pipeline, RollupOp
    from m3_tpu.metrics.policy import StoragePolicy
    from m3_tpu.metrics.rules import (
        MappingRule, RollupRule, RollupTarget, RuleSet,
    )

    sp = StoragePolicy.parse(rules["policy"])
    mapping = [
        MappingRule(
            r["name"], TagsFilter.parse("__name__:" + r["metric"]), (sp,),
            aggregation_id=AggregationID.compress(
                [AggregationType[a] for a in r["aggregations"]]))
        for r in rules["mapping"]]
    rollup = [
        RollupRule(
            r["name"], TagsFilter.parse("__name__:" + r["metric"]),
            (RollupTarget(Pipeline((
                AggregationOp(AggregationType[r["aggregation"]]),
                RollupOp(r["new_name"].encode(),
                         tuple(g.encode() for g in r["group_by"])))), (sp,)),))
        for r in rules["rollup"]]
    return RuleSet(version=1, mapping_rules=mapping, rollup_rules=rollup)


def agg_series_count(rules: dict | None, tags: list[dict]) -> int:
    """Series the rules will create in the aggregated namespace."""
    if not rules:
        return 0
    n = 0
    for r in rules["mapping"]:
        name = r["metric"].encode()
        n += len(r["aggregations"]) * sum(
            1 for t in tags if t[b"__name__"] == name)
    for r in rules["rollup"]:
        name = r["metric"].encode()
        keys = [g.encode() for g in r["group_by"]]
        n += len({tuple(t.get(k) for k in keys) for t in tags
                  if t[b"__name__"] == name})
    return n


def _pow2_capacity(n: int, shards: int) -> int:
    """Per-shard slots for n series: hash imbalance headroom, rounded up
    to a power of two (chip_smoke._pow2_capacity)."""
    return 1 << int(np.ceil(np.log2(-(-n // shards) * 1.25)))


def boot_node(cfg: dict, root: str, tags: list[dict]):
    """run_node from the configuration's node file; only the data root
    and the per-shard capacities (which follow the sizes) are filled in
    here (chip_smoke.boot_node)."""
    from m3_tpu.core.config import load_config
    from m3_tpu.server.assembly import run_node

    node = load_config(str(HERE / "configs" / cfg["node"]))
    node.db.root = root
    ns = node.db.namespaces[cfg["namespace"]]
    ns.slot_capacity = _pow2_capacity(len(tags), ns.num_shards)
    ns.sample_capacity = ns.slot_capacity * cfg["ring_points"]
    if cfg.get("agg_namespace"):
        agg = node.db.namespaces[cfg["agg_namespace"]]
        agg.slot_capacity = _pow2_capacity(
            agg_series_count(cfg["rules"], tags), agg.num_shards)
        # same ring shape for the aggregated namespace: one compile of
        # the drain serves both
        agg.sample_capacity = ns.sample_capacity
    t0 = time.monotonic()
    asm = run_node(node, ruleset=build_ruleset(cfg.get("rules")))
    say("boot", host_seconds=round(time.monotonic() - t0, 1), entry="m3_tpu.server.assembly.run_node", port=asm.port,
        mediator=asm.mediator is not None,
        downsampler=asm.downsampler is not None, shards=ns.num_shards,
        slot_capacity=ns.slot_capacity, sample_capacity=ns.sample_capacity)
    return asm


# ---------------------------------------------------------------------------
# what a generator is handed
# ---------------------------------------------------------------------------


class Cell:
    """One run of one cell.  The generator fills `log`, `spans` and
    `facts`; the harness owns the node, the clocks and the trace."""

    def __init__(self, bench, cell, cfg, traffic, seed, seconds, trace):
        self.bench, self.cell, self.cfg, self.traffic = bench, cell, cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.asm = None
        self.data = None
        self.root = None
        self.log = stats.RequestLog()
        self.spans: dict[str, list] = {}
        self.facts: dict = {}          # generator's counts for the reducers
        self.window = None             # (t0, t1) monotonic
        self.slice = None              # (t0, t1) of the traced part
        self.slice_facts: dict = {}
        self.counters: dict = {}       # devguard deltas over the window
        self.compiles_in_window = None
        self.setup_s = None
        self.trace_events = None       # reducers.tracefile.Trace
        self.generator_busy_s = 0.0    # client threads outside socket calls
        self._trace_dir = None
        self._tracing = False
        self._busy_lock = threading.Lock()

    # -- spans and annotations -------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark span: recorded on the host clock and, in a traced
        run, written into the profiler's own trace under the same name."""
        t0 = time.monotonic()
        with self.annotate(name):
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append((t0, time.monotonic()))

    def annotate(self, name: str):
        """Trace annotation only (per request: the request log has the
        times already); nothing outside the traced slice."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)

    def add_busy(self, seconds: float) -> None:
        with self._busy_lock:
            self.generator_busy_s += seconds

    # -- the traced slice --------------------------------------------------

    def slice_wanted(self) -> bool:
        """True at a point where the generator may open the slice."""
        if not self.trace or self.slice is not None or self._tracing:
            return False
        return time.monotonic() - self.window[0] >= self.traffic["trace"]["start_s"]

    def slice_full(self) -> bool:
        return (self._tracing and time.monotonic() - self._slice_t0
                >= self.traffic["trace"]["seconds"])

    def slice_open(self) -> None:
        import jax

        self._trace_dir = tempfile.mkdtemp(prefix="m3_bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host Python tracing off
        opts.host_tracer_level = 1         # the bench:* annotations only
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._tracing = True
        self._slice_t0 = time.monotonic()
        self._slice_mark = self.mark()

    def slice_close(self) -> None:
        import jax

        t1 = time.monotonic()
        since = self.since(self._slice_mark)
        self._tracing = False
        jax.profiler.stop_trace()
        self.slice = (self._slice_t0, t1)
        self.slice_facts = since

    # -- counters ----------------------------------------------------------

    def mark(self) -> dict:
        from m3_tpu.x import devguard

        return {"counters": dict(devguard.counters()),
                "facts": dict(self.facts), "rows": len(self.log.rows)}

    def since(self, mark: dict) -> dict:
        from m3_tpu.x import devguard

        now = devguard.counters()
        return {
            "counters": {k: v - mark["counters"].get(k, 0)
                         for k, v in now.items()},
            "facts": {k: v - mark["facts"].get(k, 0)
                      for k, v in self.facts.items()
                      if isinstance(v, (int, float))},
            "rows": self.log.rows[mark["rows"]:]}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def find_device(chips: int) -> dict:
    """The accelerator JAX found, or exit != 0 with no result: this
    benchmark never falls back to a CPU."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"benchmark: needs {chips} TPU chip(s); jax.devices() gave "
              f"{device}", file=sys.stderr)
        raise SystemExit(2)
    return device


def read_metric(name: str, cell: Cell):
    """A metric's reader is the file metrics/<name>.json: a reducer kind
    (reducers/<kind>.py) and its parameters.  None = nothing to read."""
    spec = load_json("metrics", name + ".json")
    reducer = importlib.import_module("benchmark.reducers." + spec["reducer"])
    return reducer.read(cell, spec.get("params", {}))


def run_cell(args, t_process: float, device: dict) -> int:
    from m3_tpu.x import devguard, jaxcache, tracewatch

    bench, cell_entry, cfg, traffic = load_cell(args.workload)
    cell = Cell(bench, cell_entry, cfg, traffic, args.seed, args.seconds,
                args.trace)
    cell.device_kind = device["kind"]
    cache_dir = jaxcache.configure()
    # every program into the cache, however quick its compile: a run
    # warms hundreds of small shapes, and set-up is what a check costs
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say("start", workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device, compile_cache=cache_dir)
    tracewatch.install(raise_on_violation=False)
    gen = importlib.import_module("benchmark.generators." + traffic["generator"])
    cell.root = tempfile.mkdtemp(prefix="m3_bench_node_")
    checks: dict = {}
    try:
        run = gen.Run(cell)
        run.setup()                       # data, boot, warm-up of every shape
        say("warm", compiles=tracewatch.total_compiles(),
            host_seconds=round(time.monotonic() - t_process, 1))
        snap = tracewatch.snapshot()
        cell.log = stats.RequestLog()     # the window's requests only
        mark = cell.mark()
        cell.setup_s = time.monotonic() - t_process
        t0 = time.monotonic()
        cell.window = (t0, None)
        with _gc_pauses() as pauses:
            run.window(args.seconds)
        if cell._tracing:
            cell.slice_close()
        cell.window = (t0, run.window_end)
        cell.compiles_in_window = tracewatch.retraces_since(snap)
        cell.counters = cell.since(mark)["counters"]
        peak = _memory_peak()
        say("window", seconds=cell.window[1] - t0,
            requests=len(cell.log.rows),
            compiles=cell.compiles_in_window,
            generator_busy_share=cell.generator_busy_s
            / max(1e-9, (cell.window[1] - t0) * traffic.get("senders", 1)),
            counters={k: v for k, v in cell.counters.items() if v},
            gc_pauses_over_50ms=pauses, peak_device_bytes=peak)
        say("latencies_ms", **{
            kind: sorted(round((r.done - r.sent) * 1e3) for r in cell.log.of(kind))
            for kind in sorted({r.kind for r in cell.log.rows})})
        t_v = time.monotonic()
        checks = run.verify(control=args.control)
        say("verify", host_seconds=round(time.monotonic() - t_v, 1),
            control=args.control or None)
    finally:
        if cell.asm is not None:
            cell.asm.close()
        tracewatch.uninstall()
        shutil.rmtree(cell.root, ignore_errors=True)

    if cell.trace:
        from benchmark.reducers import tracefile

        cell.trace_events = tracefile.load(
            cell._trace_dir, cell.slice[1] - cell.slice[0],
            traffic["trace"].get("gap_order", ()))
        shutil.rmtree(cell._trace_dir, ignore_errors=True)
    group = "per_layer" if cell.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, cell_entry["name"], group):
        v = read_metric(m["name"], cell)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    fallbacks = {k: v for k, v in devguard.counters().items()
                 if v and (k.endswith(".fallback_calls") or ".errors." in k)}
    checks["device_fallbacks"] = (sum(fallbacks.values()), 0)
    failed = sum(1 for r in cell.log.rows if not r.ok)
    checks["failed_requests"] = (failed, 0)
    compared = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared.values())
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": bool(correct), "attempted": len(cell.log.rows),
              "failed": failed, "metrics": metrics, "device": device}
    if cell.trace:
        tr = cell.trace_events
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    result["compared"] = compared
    for k, c in compared.items():
        print(f"compared {k}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


@contextlib.contextmanager
def _gc_pauses(over_s: float = 0.05):
    """The collector's long pauses while the block runs, as
    [generation, seconds at which it began, seconds it took]: the node
    lives in this process, and a stall in a window is often one of
    these.  Observation only."""
    import gc

    found, began, t0 = [], {}, time.monotonic()

    def watch(phase, info):
        if phase == "start":
            began[info["generation"]] = time.monotonic()
        else:
            b = began.pop(info["generation"], None)
            if b is not None and time.monotonic() - b >= over_s:
                found.append([info["generation"], round(b - t0, 2),
                              round(time.monotonic() - b, 3)])

    gc.callbacks.append(watch)
    try:
        yield found
    finally:
        gc.callbacks.remove(watch)


def _memory_peak() -> int:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


def main(argv=None, t_process: float | None = None) -> int:
    t_process = time.monotonic() if t_process is None else t_process
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="one of the traffic file's `controls`: that "
                         "control stands in the program's place in the "
                         "comparison, and the run has to read correct: false")
    args = ap.parse_args(argv)
    _, cell, _, traffic = load_cell(args.workload)
    if args.control and args.control not in traffic.get("controls", {}):
        raise SystemExit(f"{args.workload} has no control {args.control!r}: "
                         f"{sorted(traffic.get('controls', {}))}")
    device = find_device(cell["chips"])
    import m3_tpu  # noqa: F401 — x64 on

    return run_cell(args, t_process, device)
