"""m3lint core: findings, suppressions, baseline ratchet, the driver.

The analyzer is deliberately *codebase-aware*: its rules encode this
repo's concurrency/wire/bit-exactness contracts (see the rule modules),
not generic style.  Everything runs on stdlib ``ast`` — no third-party
dependency, so the gate works in every environment the tests do.

Baseline ratchet: findings are compared against a checked-in baseline
(`m3_tpu/tools/lint_baseline.json`) as a MULTISET of
``(rule, path, message)`` keys (line numbers are recorded for humans but
ignored in comparison, so unrelated edits that shift lines do not churn
the gate).  The gate fails on NEW findings *and* on stale baseline
entries — a fixed finding must shrink the baseline (``--update-baseline``),
so the debt curve only ratchets down.

Suppression: a finding on line N is suppressed by a trailing comment on
that line (or the line above):

    self.hits += 1  # m3lint: disable=lock-discipline
    # m3lint: disable=wire-exhaustive  (next line suppressed)

``# m3lint: disable-file=<rule>`` within the first ten lines suppresses
the rule for the whole file.  Suppressions are for *reviewed* false
positives; new debt belongs in the baseline where it is counted.
"""

from __future__ import annotations

import ast
import json
import re
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Iterable, List

RULES = (
    "lock-discipline",
    "jit-purity",
    "explicit-dtype",
    "wire-exhaustive",
    "fault-coverage",
    "resource-hygiene",
    "corruption-typed",
    "placement-cas",
    "deadline-aware",
    # the jax compile-stability/transfer families (jaxlint.py) — the
    # static twin of x/tracewatch.py
    "retrace-risk",
    "transfer-hygiene",
    "dtype-stability",
    "constant-bloat",
    # round 10: instrument-callsite hygiene (metrics_rule.py) —
    # per-call interning on hot paths, unbounded tag cardinality
    "metric-hygiene",
    # round 12: device-boundary guard coverage (devguard_rule.py) —
    # hot-path jit dispatches must run behind x.devguard
    "device-guard",
    # round 17: device-program registry completeness (registry_rule.py)
    # — devguard entry points × membudget components × costwatch
    # stages must describe the same program set
    "registry-complete",
    # round 18: self-healing actuator discipline (actuator_rule.py) —
    # control-plane knobs (admission capacity, membudget budget,
    # breaker thresholds/state, forced fallback) mutate only through
    # x/controller.py's typed actuator registry
    "actuator-typed",
    # round 20: typed disk-capacity errors (capacity_rule.py) —
    # durable write ops in persist/ (+ the aggregator checkpoint) run
    # inside capacity_guard so ENOSPC/EDQUOT classify into
    # DiskCapacityError with temp cleanup and counters, never escape
    # as raw OSError
    "enospc-typed",
)

_SUPPRESS_RE = re.compile(r"#\s*m3lint:\s*disable=([\w,-]+)")
_SUPPRESS_FILE_RE = re.compile(r"#\s*m3lint:\s*disable-file=([\w,-]+)")


@dataclass(frozen=True, order=True)
class Finding:
    rule: str
    path: str      # posix path relative to the repo root (e.g. m3_tpu/x/fault.py)
    line: int
    message: str

    @property
    def key(self):
        """Baseline identity: line numbers drift with unrelated edits,
        (rule, path, message) survives them."""
        return (self.rule, self.path, self.message)

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass
class Context:
    """Scope knobs the rules consult.  Paths are posix, relative to the
    repo root; prefixes select rule applicability per file.  The corpus
    tests pass permissive prefixes so every rule fires on the seeded
    violations regardless of where the corpus lives."""

    # round 8: aggregator/ joined the dtype scope — the packed arena's
    # word formats (u64 lanes, orderable-f32 words, o16 minmax) are
    # bit-layout contracts exactly like the codec's
    dtype_prefixes: tuple = ("m3_tpu/encoding/", "m3_tpu/parallel/",
                             "m3_tpu/aggregator/")
    # round 12: dtest/ joined the wire scope — the soak/chaos harness
    # drives live clusters, and a raw socket op in IT would be a fault
    # injection the faultpoint registry can't see or replay (chaos must
    # stay scripted through named faultpoints, not ad-hoc socket pokes)
    wire_prefixes: tuple = ("m3_tpu/server/", "m3_tpu/client/",
                            "m3_tpu/cluster/", "m3_tpu/msg/",
                            "m3_tpu/dtest/")
    wire_files: tuple = ("m3_tpu/persist/commitlog.py",)
    # The framing module IS the designated low-level seam: raw socket
    # ops are legal only here (everything else reaches them through
    # send_frame/recv_frame behind a named faultpoint).
    fault_helper_files: tuple = ("m3_tpu/msg/protocol.py",)
    # files whose module-level small-int constants must be registered
    # in a wirecheck dispatch family (the family-table ratchet)
    constant_files: tuple = ("m3_tpu/msg/protocol.py",
                             "m3_tpu/server/rpc.py",
                             "m3_tpu/server/ingest_tcp.py",
                             "m3_tpu/cluster/kv_remote.py",
                             "m3_tpu/query/remote.py")
    # files whose digest/checksum/magic verify sites must raise the
    # typed CorruptionError hierarchy, never a bare ValueError
    persist_prefixes: tuple = ("m3_tpu/persist/",)
    # the blessed home of raw placement-key KV mutations; everywhere
    # else must go through PlacementService (placement-cas rule)
    placement_files: tuple = ("m3_tpu/cluster/placement.py",)
    # query-path modules whose blocking wire calls must flow through a
    # deadline-accepting helper (deadline-aware rule); prefixes let the
    # seeded corpus opt in wholesale
    deadline_files: tuple = ("m3_tpu/query/remote.py",
                             "m3_tpu/server/rpc.py",
                             "m3_tpu/client/session.py")
    deadline_prefixes: tuple = ()
    # the numeric/device layer the jax families police (transfer-
    # hygiene's module-scope checks); chip_smoke.py sits outside the linted
    # package and is covered by the runtime twin (tracewatch) instead
    jax_prefixes: tuple = ("m3_tpu/encoding/", "m3_tpu/parallel/",
                          "m3_tpu/aggregator/")
    # declared host boundaries: the scalar codec and the ops tools own
    # device->host transfers; everything else returns device arrays
    jax_host_boundary: tuple = ("m3_tpu/tools/", "m3_tpu/encoding/m3tsz.py")
    # modules whose perf_counter-timed regions must block_until_ready
    timed_prefixes: tuple = ("m3_tpu/tools/",)
    # request-serving trees where instrument interning must be hoisted
    # out of loops/handlers and tag values must be literals
    # (metric-hygiene rule); maintenance paths may intern lazily.
    # round 14: the self-monitoring loop joined the scope — selfmon
    # converts SCRAPED samples into storage writes every tick, and a
    # label passthrough into `.tagged({...})` there would intern one
    # registry series per scraped label value (the exact unbounded-
    # cardinality leak the rule exists to stop); coordinator/ joined
    # because the downsampler sits on the same per-batch ingest path
    metric_prefixes: tuple = ("m3_tpu/server/", "m3_tpu/query/",
                              "m3_tpu/instrument/selfmon.py",
                              "m3_tpu/coordinator/")
    # known large host arrays (constant-bloat flags references to these
    # under the tracer even across modules, where size can't be folded)
    large_constants: tuple = ("_VALUE_CTRL_TBL",)
    # round 12: serving-hot-path trees whose raw device dispatches
    # (module-jitted names, device_put, block_until_ready) must flow
    # through the x.devguard seam (device-guard rule).  parallel/ is
    # out of scope by design: its shard_map bodies compose raw() ops
    # in-trace, and its host wrappers are themselves the guarded seam.
    device_prefixes: tuple = ("m3_tpu/server/", "m3_tpu/storage/",
                              "m3_tpu/aggregator/")
    # files that ARE the guard plumbing (nothing today; the seam lives
    # in x/devguard.py, outside the scoped prefixes)
    device_helper_files: tuple = ()
    # round 17: trees whose run_guarded/membudget literals must be
    # declared in registry_rule.FAMILIES (registry-complete rule); the
    # costwatch registry file additionally cross-checks the inverse
    # direction (every family has a cost leg or a reviewed waiver)
    registry_prefixes: tuple = ("m3_tpu/storage/", "m3_tpu/aggregator/",
                                "m3_tpu/encoding/", "m3_tpu/server/")
    registry_cost_file: str = "m3_tpu/x/costwatch.py"
    # round 18: the blessed homes of control-plane mutation verbs
    # (actuator-typed rule): the controller's actuator registry itself,
    # devguard (force_fallback drives force_open — plumbing under the
    # seam), and assembly (boot-time configuration from validated
    # config is initialization, not runtime mutation)
    controller_files: tuple = ("m3_tpu/x/controller.py",
                               "m3_tpu/x/devguard.py",
                               "m3_tpu/server/assembly.py")
    # round 20: trees whose durable write ops (fsync/replace/write-mode
    # opens) must run inside capacity_guard (enospc-typed rule); the
    # guard module itself is the blessed classification seam and exempt
    capacity_prefixes: tuple = ("m3_tpu/persist/",
                                "m3_tpu/aggregator/checkpoint.py")
    capacity_helper_files: tuple = ("m3_tpu/persist/capacity.py",)

    def is_wire_module(self, path: str) -> bool:
        return (path in self.wire_files
                or any(path.startswith(p) for p in self.wire_prefixes))

    def wants_dtype(self, path: str) -> bool:
        return any(path.startswith(p) for p in self.dtype_prefixes)

    def is_persist_module(self, path: str) -> bool:
        return any(path.startswith(p) for p in self.persist_prefixes)

    def wants_jax(self, path: str) -> bool:
        return any(path.startswith(p) for p in self.jax_prefixes)

    def is_host_boundary(self, path: str) -> bool:
        return any(path.startswith(p) for p in self.jax_host_boundary)

    def wants_timed(self, path: str) -> bool:
        return any(path.startswith(p) for p in self.timed_prefixes)

    def is_capacity_module(self, path: str) -> bool:
        if path in self.capacity_helper_files:
            return False
        return any(path.startswith(p) for p in self.capacity_prefixes)


@dataclass
class FileUnit:
    """One parsed file handed to every rule."""

    path: str            # repo-relative posix
    tree: ast.AST
    source: str
    lines: List[str] = field(default_factory=list)

    def __post_init__(self):
        if not self.lines:
            self.lines = self.source.splitlines()


Rule = Callable[[FileUnit, Context], List[Finding]]


def _suppressions(unit: FileUnit):
    per_line: dict[int, set] = {}
    file_wide: set = set()
    for i, text in enumerate(unit.lines, start=1):
        m = _SUPPRESS_RE.search(text)
        if m:
            rules = set(m.group(1).split(","))
            per_line.setdefault(i, set()).update(rules)
            # a comment-only line also suppresses the line below it
            if text.lstrip().startswith("#"):
                per_line.setdefault(i + 1, set()).update(rules)
        if i <= 10:
            mf = _SUPPRESS_FILE_RE.search(text)
            if mf:
                file_wide.update(mf.group(1).split(","))
    return per_line, file_wide


def apply_suppressions(unit: FileUnit, findings: Iterable[Finding]) -> List[Finding]:
    per_line, file_wide = _suppressions(unit)
    out = []
    for f in findings:
        if f.rule in file_wide or "all" in file_wide:
            continue
        rules = per_line.get(f.line, ())
        if f.rule in rules or "all" in rules:
            continue
        out.append(f)
    return out


def default_rules() -> List[Rule]:
    from m3_tpu.x.lint import (
        actuator_rule, capacity_rule, corruption, deadline_aware,
        devguard_rule, faultcov, jaxlint, locks, metrics_rule,
        placement, purity, registry_rule, resources, wirecheck,
    )

    return [
        locks.check,
        purity.check_jit_purity,
        purity.check_explicit_dtype,
        wirecheck.check,
        faultcov.check,
        resources.check,
        corruption.check,
        placement.check,
        deadline_aware.check,
        jaxlint.check_retrace,
        jaxlint.check_transfer,
        jaxlint.check_dtype_stability,
        jaxlint.check_constant_bloat,
        metrics_rule.check,
        devguard_rule.check,
        registry_rule.check,
        actuator_rule.check,
        capacity_rule.check,
    ]


def explain(rule: str) -> dict | None:
    """{why, bad, good} for a rule name, harvested from the rule
    modules' EXPLAIN tables (``cli lint --explain`` renders it)."""
    from m3_tpu.x.lint import (
        actuator_rule, capacity_rule, corruption, deadline_aware,
        devguard_rule, faultcov, jaxlint, locks, metrics_rule,
        placement, purity, registry_rule, resources, wirecheck,
    )

    for mod in (jaxlint, locks, purity, wirecheck, faultcov, resources,
                corruption, placement, deadline_aware, metrics_rule,
                devguard_rule, registry_rule, actuator_rule,
                capacity_rule):
        entry = getattr(mod, "EXPLAIN", {}).get(rule)
        if entry is not None:
            return entry
    return None


def lint_file(path: Path, rel_root: Path, ctx: Context,
              rules: List[Rule] | None = None) -> List[Finding]:
    source = path.read_text(encoding="utf-8")
    rel = path.relative_to(rel_root).as_posix()
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        return [Finding("parse-error", rel, e.lineno or 0, str(e.msg))]
    unit = FileUnit(rel, tree, source)
    findings: List[Finding] = []
    for rule in (rules if rules is not None else default_rules()):
        findings.extend(rule(unit, ctx))
    return apply_suppressions(unit, findings)


def lint_tree(root: Path, rel_root: Path | None = None,
              ctx: Context | None = None,
              rules: List[Rule] | None = None) -> List[Finding]:
    """Lint every ``*.py`` under ``root``; paths reported relative to
    ``rel_root`` (default: root's parent, so scanning ``<repo>/m3_tpu``
    yields ``m3_tpu/...`` paths matching the Context prefixes)."""
    root = Path(root)
    rel_root = Path(rel_root) if rel_root is not None else root.parent
    ctx = ctx or Context()
    findings: List[Finding] = []
    for path in sorted(root.rglob("*.py")):
        findings.extend(lint_file(path, rel_root, ctx, rules))
    return sorted(findings)


# -- baseline ratchet --------------------------------------------------------


def default_baseline_path() -> Path:
    import m3_tpu.tools as _tools

    return Path(_tools.__file__).resolve().parent / "lint_baseline.json"


def load_baseline(path: Path) -> List[Finding]:
    if not Path(path).exists():
        return []
    raw = json.loads(Path(path).read_text())
    return [Finding(f["rule"], f["path"], int(f.get("line", 0)), f["message"])
            for f in raw.get("findings", [])]


def save_baseline(path: Path, findings: Iterable[Finding]) -> None:
    payload = {
        "version": 1,
        "findings": [
            {"rule": f.rule, "path": f.path, "line": f.line,
             "message": f.message}
            for f in sorted(findings)
        ],
    }
    Path(path).write_text(json.dumps(payload, indent=1) + "\n")


def diff_baseline(findings: Iterable[Finding], baseline: Iterable[Finding]):
    """Returns (new, fixed): findings not in the baseline, and baseline
    entries that no longer fire.  Multiset semantics — two identical
    findings in one file need two baseline entries."""
    cur = Counter(f.key for f in findings)
    base = Counter(f.key for f in baseline)
    by_key: dict = {}
    for f in findings:
        by_key.setdefault(f.key, f)
    for f in baseline:
        by_key.setdefault(f.key, f)
    new = []
    fixed = []
    for key in (cur - base):
        for _ in range((cur - base)[key]):
            new.append(by_key[key])
    for key in (base - cur):
        for _ in range((base - cur)[key]):
            fixed.append(by_key[key])
    return sorted(new), sorted(fixed)


# -- shared AST helpers ------------------------------------------------------


def dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def func_defs(tree: ast.AST):
    """Every FunctionDef/AsyncFunctionDef in the tree (any nesting)."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node
