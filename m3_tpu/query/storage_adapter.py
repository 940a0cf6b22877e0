"""Storage adapter: PromQL selectors → index query → raw series blocks.

Equivalent of `src/query/storage/m3` (FetchCompressed
`m3/storage.go:215-225`: label matchers → index FetchTagged → decoded
series) without the network hop — the engine and the database share a
process, as in the reference's embedded coordinator mode.  A selector's
series cross the `DatabaseStorage` -> `Database` seam once, as a batch,
and arrive as the block's columns (`Database.read_columns`).
"""

from __future__ import annotations

import contextlib

import numpy as np

from m3_tpu.index.search import (
    All, Conjunction, Negation, Query, Regexp, Term,
)
from m3_tpu.instrument import tracing
from m3_tpu.instrument.tracing import Tracepoint
from m3_tpu.query.block import RawBlock, SeriesMeta
from m3_tpu.query.promql import LabelMatcher
from m3_tpu.storage.database import Database
from m3_tpu.x import deadline as xdeadline
from m3_tpu.x import fault

# reusable no-op scope for the unbound-deadline fast path
_NULL_PHASE = contextlib.nullcontext()


def matchers_to_query(name: bytes | None,
                      matchers: tuple[LabelMatcher, ...]) -> Query:
    """Label matchers → boolean index query (reference storage/m3
    FetchOptionsToM3Options + idx query conversion)."""
    parts: list[Query] = []
    if name is not None:
        parts.append(Term(b"__name__", name))
    for m in matchers:
        if m.op == "=":
            parts.append(Term(m.name, m.value))
        elif m.op == "!=":
            parts.append(Negation(Term(m.name, m.value)))
        elif m.op == "=~":
            parts.append(Regexp(m.name, m.value))
        elif m.op == "!~":
            parts.append(Negation(Regexp(m.name, m.value)))
        else:
            raise ValueError(f"bad matcher op {m.op}")
    if not parts:
        return All()
    if len(parts) == 1 and not isinstance(parts[0], Negation):
        return parts[0]
    return Conjunction(*parts)


class DatabaseStorage:
    """Engine Storage implementation over one Database namespace."""

    def __init__(self, db: Database, namespace: str = "default"):
        self.db = db
        self.namespace = namespace

    def fetch_raw(self, name, matchers, start_nanos, end_nanos) -> RawBlock:
        # The read path's deterministic injection point: delay = slow
        # storage/peer (the overload dtest arms this on one replica),
        # error = failed fetch.  Fired here so BOTH local engine reads
        # and federation-served remote fetches cross one boundary.
        fault.fire("query.fetch")
        dl = xdeadline.current()
        with (dl.phase("fetch") if dl is not None
              else _NULL_PHASE), tracing.span(Tracepoint.FETCH_COMPRESSED):
            return self._fetch_raw(name, matchers, start_nanos, end_nanos)

    def _fetch_raw(self, name, matchers, start_nanos, end_nanos) -> RawBlock:
        q = matchers_to_query(name, matchers)
        docs = self.db.query_ids(self.namespace, q, start_nanos, end_nanos)
        with tracing.span(Tracepoint.STORAGE_METAS) as sp:
            docs.sort(key=lambda d: d.id)
            ids = [d.id for d in docs]
            metas = [series_meta(d) for d in docs]
            if sp.recording:
                sp.set_tag("n", len(metas))
        # cancellable before the batch; the database checks between shards
        xdeadline.check_current("fetch series")
        cols = self.db.read_columns(self.namespace, ids, start_nanos,
                                    end_nanos)
        if len(cols.index) != len(metas):
            # rows are the series of the shards this node owns
            metas = [metas[i] for i in cols.index.tolist()]
        return RawBlock(cols.ts, cols.values, cols.counts, metas)


def series_meta(doc) -> SeriesMeta:
    """A document's labels as a query block's series meta, built once
    and kept on the index's document: a dashboard over the whole fleet
    asks for every series' meta on every refresh."""
    m = doc.__dict__.get("_series_meta")
    if m is None:
        m = SeriesMeta(tuple(sorted(doc.tags().items())))
        object.__setattr__(doc, "_series_meta", m)
    return m


class SessionStorage:
    """Engine Storage over a ReplicatedSession: the coordinator-style
    deployment where the query engine reaches storage through the
    replica-merging client (`query/storage/m3/storage.go:215-225`
    FetchCompressed → session.FetchTagged)."""

    def __init__(self, session, namespace: str = "default"):
        self.session = session
        self.namespace = namespace

    def fetch_raw(self, name, matchers, start_nanos, end_nanos) -> RawBlock:
        fault.fire("query.fetch")
        q = matchers_to_query(name, matchers)
        docs = self.session.query_ids(self.namespace, q, start_nanos, end_nanos)
        pts = []
        for i, d in enumerate(docs):
            if i % 64 == 0:  # per-series replica fan-out: cancellable
                xdeadline.check_current("fetch series")
            pts.append(
                self.session.fetch(self.namespace, d.id, start_nanos,
                                   end_nanos))
        metas = [SeriesMeta(tuple(sorted(d.tags().items()))) for d in docs]
        return RawBlock.from_lists(pts, metas)
