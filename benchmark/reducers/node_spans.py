"""The node's own spans (the ring of ``m3_tpu/instrument/tracing.py``,
``cell.asm.tracer``) over the traced slice: parent links, and self time
= a span's duration minus the part of it that its children cover.

A span records only while the profiler session of the slice is live, so
the ring holds the slice and little else.  Work is counted from the
spans' own roots (a root = a span with no parent: one ``api.write``,
``api.queryRange`` or ``mediator.runOnce`` each), and only from roots
that lie wholly inside the slice: a request in flight when the slice
opened carries no spans, one in flight when it closed has no end yet.

A per-unit reader counts only below the roots that count its unit
(``ROOT_OF``): a span that opens under every kind of root, as
``db.lock.wait`` does, is a write's wait per ksample, a query's per
query and a pass's per pass, and never the one path's spans over the
other's work.

``load`` gives None — and every reducer built on it then reports
nothing — where the program has no such tracer (the parent of the PR
that added it), the run was not traced, or the ring pushed out spans
that may lie inside the slice.
"""

from __future__ import annotations

from benchmark.reducers.tracefile import _union


class Node:
    __slots__ = ("name", "t0", "t1", "tags", "cpu", "parent", "children")

    def __init__(self, name, t0, t1, tags, cpu=0.0):
        self.name, self.t0, self.t1, self.tags = name, t0, t1, tags
        self.cpu = cpu              # CPU seconds of the span's thread
        self.parent, self.children = None, []

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def self_seconds(self) -> float:
        """Duration minus what the children cover of it: children may
        overlap one another (threads) and may outlive the parent."""
        covered = _union((max(c.t0, self.t0), min(c.t1, self.t1))
                         for c in self.children
                         if c.t1 > self.t0 and c.t0 < self.t1)
        return self.seconds - covered

    @property
    def self_cpu_seconds(self) -> float:
        """CPU seconds of the span's thread not spent under a child (a
        span and its children run on one thread).  Under one GIL these
        add up over concurrent requests; wall self times do not, since
        each includes its thread's wait for the GIL."""
        return max(0.0, self.cpu - sum(c.cpu for c in self.children))


# the root whose work a per-unit metric divides by: `api.write` counts
# samples (tag `n`), the others one a root
ROOT_OF = {"ksample": "api.write", "query": "api.queryRange",
           "pass": "mediator.runOnce"}


def matches(name: str, patterns) -> bool:
    """A pattern is a span's name, or a prefix ending in ``*``."""
    return any(name == p or (p.endswith("*") and name.startswith(p[:-1]))
               for p in patterns)


def build(rows) -> list[Node]:
    """Nodes, linked, from (span_id, parent_id, name, t0_s, t1_s, tags[,
    cpu_s])."""
    rows = list(rows)
    by_id = {r[0]: Node(*r[2:]) for r in rows}
    for sid, pid, *_ in rows:
        parent = by_id.get(pid)
        if parent is not None:
            by_id[sid].parent = parent
            parent.children.append(by_id[sid])
    return list(by_id.values())


class Spans:
    def __init__(self, nodes: list[Node], t0: float, t1: float):
        self.t0, self.t1 = t0, t1
        # every span that touches the slice (for unions over its time)
        self.touching = [n for n in nodes if n.t1 > t0 and n.t0 < t1]
        self.roots = [n for n in nodes
                      if n.parent is None and n.t0 >= t0 and n.t1 <= t1]

    def under_roots(self, names=None) -> list[Node]:
        """The roots that lie inside the slice, and all below them; only
        the roots named in `names` and what lies below them, where it is
        given."""
        out = []
        todo = [r for r in self.roots if names is None or r.name in names]
        while todo:
            n = todo.pop()
            out.append(n)
            todo.extend(n.children)
        return out

    def by_name(self) -> dict:
        """{span name: [count, wall seconds, wall self seconds, CPU self
        seconds]} under the slice's roots: the whole account, for the
        free-form line that PERF.md's breakdown is written from."""
        acc: dict = {}
        for n in self.under_roots():
            row = acc.setdefault(n.name, [0, 0.0, 0.0, 0.0])
            row[0] += 1
            row[1] += n.seconds
            row[2] += n.self_seconds
            row[3] += n.self_cpu_seconds
        return acc

    def work(self, per: str) -> float:
        """What the slice's own roots counted, in the unit a per-unit
        metric divides by."""
        roots = [r for r in self.roots if r.name == ROOT_OF[per]]
        if per == "ksample":
            return sum(r.tags.get("n", 0) for r in roots) / 1e3
        return len(roots)


def load(cell) -> Spans | None:
    tracer = getattr(cell.asm, "tracer", None)
    if tracer is None or not cell.trace or cell.slice is None:
        return None
    if getattr(tracer, "dropped", None) is None:
        return None                  # a tracer that cannot say what it lost
    t0, t1 = cell.slice
    if tracer.dropped and tracer.dropped_until_ns / 1e9 > t0:
        return None                  # truncated inside the slice
    nodes = build((s.span_id, s.parent_id, s.name, s.start_ns / 1e9,
                   s.end_ns / 1e9, s.tags, getattr(s, "cpu_ns", 0) / 1e9)
                  for s in tracer.finished())
    return Spans(nodes, t0, t1) if nodes else None
