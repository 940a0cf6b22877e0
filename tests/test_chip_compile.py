"""Compile-only guards for the chip the node runs on: every program on
the served path, at the widths the node uses, through the TPU compiler
for a DESCRIBED v5e (no chip attached, nothing runs).

This is the rehearsal that costs no chip time: it raises what the
chip's compiler would raise — a Pallas block the Mosaic lowering
refuses, a 64-bit bitcast the X64 rewriter has no rule for, a program
that does not fit the device.  A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports this file), and all of it lives in this one file so one
worker owns the library.
"""

import collections
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import m3_tpu  # noqa: F401 — x64 on
from m3_tpu.query.engine import _RANGE_BLOCK_ROWS

# The node's widths: T = 240-point scrapes in 2 h blocks; the flush
# encodes one shard's series per call (chip_smoke: ~26K of 104K over 4
# shards; 8192 rows is the per-call width ISSUE 22 names) into
# out_words = max(16, T * 40 // 64 + 8) u64 words (storage/database.py).
S, T = 8192, 240
_BLOCK = _RANGE_BLOCK_ROWS   # the engine's rows a range call
OW = max(16, T * 40 // 64 + 8)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


class A:
    """One array argument: shape + dtype (a leaf for tree_map)."""

    def __init__(self, shape, dtype):
        self.shape, self.dtype = tuple(shape), dtype


def _compile(fn, one_chip, *shapes, **static):
    """Lower + compile `fn` for the described chip; prints seconds and
    temporaries (`pytest -s` shows them: the numbers CHANGES.md cites)."""
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    t0 = time.monotonic()
    compiled = fn.lower(*args, **static).compile()
    mem = compiled.memory_analysis()
    print(f"\n[chip-compile] {getattr(fn, '__name__', fn)} {static}: "
          f"{time.monotonic() - t0:.1f}s, temp "
          f"{mem.temp_size_in_bytes / 2**20:.0f} MiB, args "
          f"{mem.argument_size_in_bytes / 2**20:.0f} MiB")
    return compiled


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


_NAME = r"%([\w.\-]+)"


def _loop_gathers(compiled) -> collections.Counter:
    """The gathers inside the compiled program's while loops (bodies,
    conditions and whatever they call), counted by their operand's
    type as the HLO text states it, e.g. ``u32[512,144]``."""
    comps = {}
    for block in re.split(r"\n(?=\S)", compiled.as_text()):
        m = re.match(r"(?:ENTRY )?" + _NAME, block)
        if m:
            comps[m.group(1)] = block
    todo = [b for blk in comps.values()
            for b in re.findall(r"\bbody=" + _NAME, blk)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += re.findall(r"(?:calls|body|condition|to_apply)=" + _NAME,
                           comps[name])
    found = collections.Counter()
    for name in seen:
        blk = comps[name]
        types = dict(re.findall(_NAME + r" = (\w+\[[\d,]*\])", blk))
        for op in re.findall(r"= \S+ gather\(" + _NAME, blk):
            found[types.get(op, "?")] += 1
    return found


class TestPallasKernels:
    """Every Pallas kernel `auto` can select on a TPU, compiled by
    Mosaic (interpret=False) at the node's widths."""

    @pytest.mark.parametrize("rows", [8, 1024, S])
    def test_decode_gather(self, one_chip, rows):
        from m3_tpu.parallel import pallas_decode as pd

        w32 = 2 * (OW + 16)          # u32 halves of the padded stream
        c = _compile(pd._gather3_pallas, one_chip,
                     A((rows, w32), jnp.uint32), A((rows, 2 * T), jnp.int32),
                     interpret=False)
        assert _has_kernel(c)

    @pytest.mark.parametrize("rows", [8, 1024, S])
    def test_encode_place(self, one_chip, rows):
        from m3_tpu.parallel import pallas_encode as pe

        f32 = 2 * 2 * 4 * T          # hi+lo fragments, two u32 halves each
        c = _compile(pe._place_pallas, one_chip,
                     A((rows, f32), jnp.uint32), A((rows, f32), jnp.int32),
                     w32=2 * OW, interpret=False)
        assert _has_kernel(c)


class TestCodecPrograms:
    @pytest.mark.parametrize("place", ["pallas", "gather", "scatter"])
    def test_encode_batch_device(self, one_chip, place, monkeypatch):
        from m3_tpu.encoding import m3tsz_jax as mj
        from m3_tpu.parallel import pallas_encode

        # the process' backend is the CPU, so the seam would pick
        # interpret mode: steer it as the chip would resolve it
        monkeypatch.setattr(pallas_encode, "auto_interpret", lambda: False)

        c = _compile(mj._encode_batch_device, one_chip,
                     A((S, T), jnp.int64), A((S, T), jnp.uint64),
                     A((S,), jnp.int64), A((S, T), jnp.bool_),
                     unit=1, out_words=OW, place=place)
        assert _has_kernel(c) == (place == "pallas")

    @pytest.mark.parametrize("chains,extract", [
        ("gather", "pallas"), ("gather", "jnp"), ("fused", "jnp")])
    def test_decode_batch_device(self, one_chip, chains, extract,
                                 monkeypatch):
        from m3_tpu.encoding import m3tsz_jax as mj
        from m3_tpu.parallel import pallas_decode

        monkeypatch.setattr(pallas_decode, "auto_interpret", lambda: False)

        c = _compile(mj._decode_batch_device, one_chip,
                     A((S, OW), jnp.uint64), A((S,), jnp.int64),
                     A((1 << 18,), jnp.uint32),
                     max_points=T + 8, default_unit=1, chains=chains,
                     scan_major=True, extract=extract)
        assert _has_kernel(c) == (extract == "pallas")

    @pytest.mark.parametrize("regfile,words", [
        pytest.param(rf, w, id=str(w) if rf == "gather" else f"{rf}-{w}")
        for rf in ("gather", "select") for w in (128, 192, 768)])
    def test_decode_a_fetch_of_a_sealed_block(self, one_chip, regfile,
                                              words, monkeypatch):
        """The served read path's decode (storage/database.py
        `_decode_streams`) at `prom.dashboard_flushed`'s shape: 390
        series a panel in a row bucket of 512, a full 2 h block of 720
        points, counter streams in a word bucket of 128 or 192 (768:
        the extreme-value family's), the tail `chains="auto"` resolves
        to on a TPU, under both register-file formulations.  `select`
        (what a TPU resolves) leaves no gather of the stream words in
        the scan and no more temporaries than `gather`."""
        from m3_tpu.encoding import m3tsz_jax as mj
        from m3_tpu.parallel import pallas_decode
        from m3_tpu.storage import database

        monkeypatch.setattr(pallas_decode, "auto_interpret", lambda: False)
        assert 512 % database._ROW_BUCKET == 0
        assert words % database._WORD_BUCKET == 0
        assert 720 % database._POINT_BUCKET == 0

        def compiled(rf):
            return _compile(mj._decode_batch_device, one_chip,
                            A((512, words), jnp.uint64), A((512,), jnp.int64),
                            A((1 << 18,), jnp.uint32),
                            max_points=720, default_unit=1, chains="gather",
                            scan_major=True, extract="pallas", regfile=rf)

        c = compiled(regfile)
        assert _has_kernel(c)
        # the padded stream words, split into u32 halves on the chip
        words_op = f"u32[512,{words + mj._PAD_WORDS}]"
        in_loop = _loop_gathers(c)
        if regfile == "gather":
            assert in_loop[words_op] > 0, in_loop
        else:
            assert in_loop[words_op] == 0, in_loop
            temp = c.memory_analysis().temp_size_in_bytes
            assert temp <= compiled("gather").memory_analysis() \
                .temp_size_in_bytes


class TestStoragePrograms:
    # a 2-window ring of 1M samples per shard (chip_smoke's node at
    # ~16K series over 4 shards); compile seconds grow with the ring
    # and with every lane a sort carries: see storage/buffer.py
    W, CAP = 2, 1 << 20

    def _state(self):
        from m3_tpu.storage.buffer import BufferState

        return BufferState(
            slot=A((self.W, self.CAP), jnp.int32),
            ts=A((self.W, self.CAP), jnp.int64),
            val=A((self.W, self.CAP), jnp.uint64),
            n=A((self.W,), jnp.int64))

    def test_buffer_append(self, one_chip):
        from m3_tpu.storage import buffer

        n = 4_096                    # one scrape's share of one shard
        _compile(buffer.buffer_append, one_chip, self._state(),
                 A((n,), jnp.int32), A((n,), jnp.int32), A((n,), jnp.int64),
                 A((n,), jnp.uint64))

    def test_buffer_drain(self, one_chip):
        from m3_tpu.storage import buffer

        _compile(buffer.buffer_drain, one_chip, self._state(),
                 A((), jnp.int32))


class TestAggregatorPrograms:
    """The packed gauge arena the downsampler feeds (HTTP writes carry
    no metric type, so every sample lands in the gauge arena)."""

    W, C, N = 4, 1 << 16, 16_384

    def _state(self):
        from m3_tpu.aggregator import packed

        shapes = jax.eval_shape(lambda: packed.gauge_init(self.W, self.C))
        return jax.tree_util.tree_map(lambda a: A(a.shape, a.dtype), shapes)

    def test_gauge_ingest(self, one_chip):
        from m3_tpu.aggregator import packed

        _compile(packed.gauge_ingest, one_chip, self._state(),
                 A((self.N,), jnp.int64), A((self.N,), jnp.float64),
                 A((self.N,), jnp.int64), A((self.N,), jnp.int64),
                 num_windows=self.W, capacity=self.C)

    def test_gauge_consume(self, one_chip):
        from m3_tpu.aggregator import packed

        _compile(packed.gauge_consume, one_chip, self._state(),
                 A((), jnp.int32), capacity=self.C)


class TestServedAggregatorPrograms:
    """The standalone aggregator's arenas at the shapes of the cell
    m3agg.untimed_rollup (run_aggregator; 131,072 series): one frame is
    one counter_ingest and one gauge_ingest call of 4,096 samples over
    2 windows x 2^16 slots per type."""

    W, C, N = 2, 1 << 16, 4096

    def _state(self, init):
        shapes = jax.eval_shape(lambda: init(self.W, self.C))
        return jax.tree_util.tree_map(lambda a: A(a.shape, a.dtype), shapes)

    # small batches too: a jnp.nonzero(size=K) with K cut to a batch of
    # N <= 1,024, under the overflow pool's cond, ran the TPU compiler
    # out of scoped VMEM in its 64-bit cumsum (PR 28: found on the chip)
    @pytest.mark.parametrize("n", [N, 1024, 256])
    def test_counter_ingest(self, one_chip, n):
        from m3_tpu.aggregator import packed

        _compile(packed.counter_ingest, one_chip,
                 self._state(packed.counter_init),
                 A((n,), jnp.int64), A((n,), jnp.int64),
                 A((n,), jnp.int64),
                 num_windows=self.W, capacity=self.C)

    def test_counter_consume(self, one_chip):
        from m3_tpu.aggregator import packed

        _compile(packed.counter_consume, one_chip,
                 self._state(packed.counter_init), A((), jnp.int32),
                 capacity=self.C)


class TestTimerDrain:
    """packed.timer_consume, every drain's program (the downsampler's
    passes and m3agg.untimed_rollup drain it empty, m3agg.timer_quantile
    full): its two binary searches gather from the sorted slot column
    log2 S times each, so that column has to sit in fast memory.  When
    the moments' scatters (or their conditional) shared it, the TPU
    compiler left it in HBM and an empty drain at the downsampler's
    2^18 words took 80 ms for 25 (PR 31, measured on a v5e; the layouts
    below read the same here, at any size)."""

    W, S, C = 2, 1 << 14, 1 << 12

    def test_searches_gather_from_fast_memory(self, one_chip):
        """Also with the `moments` predicate (a drain whose slots ask for
        no moment skips them too): the empty drain keeps the layout that
        made it fast, so it is no slower, and one program serves both
        values of the predicate, a parameter of the entry, not a
        constant folded into it."""
        import re

        from m3_tpu.aggregator import packed

        shapes = jax.eval_shape(
            lambda: packed.timer_init(self.W, self.C, self.S))
        state = jax.tree_util.tree_map(lambda a: A(a.shape, a.dtype), shapes)
        compiled = _compile(packed.timer_consume, one_chip, state,
                            A((), jnp.int32), A((), jnp.bool_),
                            capacity=self.C, quantiles=(0.5, 0.95, 0.99))
        text = compiled.as_text()
        entry = text[text.index("ENTRY"):]
        loops = [line.split(" while(")[0] for line in entry.splitlines()
                 if " while(" in line]
        assert len(loops) == 2            # searchsorted left and right
        for carried in loops:
            (column,) = re.findall(r"s32\[%d\][^,]*" % self.S, carried)
            assert "S(1)" in column, column
        # an empty window, or one whose slots ask for no moment, skips them
        assert entry.count(" conditional(") == 1
        assert re.search(r"pred\[\][^\n]* parameter\(", entry)


class TestQueryPrograms:
    """rate -> sum by (le) -> histogram_quantile is three device
    programs: the rate stencil, the group reduction (a segmented scan
    on the host's sorted plan) and the histogram_quantile kernel."""

    @pytest.mark.parametrize("series,points,steps", [
        (S, T, T),            # the node's width
        (395, 256, T),        # a panel of prom.dashboard_live
        (390, 720, T),        # a panel of prom.dashboard_flushed
        (6250, 256, T),       # every bucket series of the fleet at once
        (_BLOCK, 256, T),     # one row block of prom.fleet_quantile
    ])
    def test_rate(self, one_chip, series, points, steps):
        """Window ends by comparison (PR 34): no binary search (a
        `while` of log2(P) dependent gather rounds) and no per-element
        gather is left in the program.  Both steps of the issue ship:
        the bounds count, the five end reads are a select against
        iota(P) and a max over P (measured on the chip at all three
        shapes and kept: 1.4 / 2.5 / 24.8 ms a call against 10.0 / 9.9
        / 161 with the ten gathers; PERF.md section 6).  The (S, P, T)
        comparison is fused into its reduction, never stored."""
        from m3_tpu.query import temporal

        c = _compile(temporal.rate_family, one_chip,
                     A((series, points), jnp.int64),
                     A((series, points), jnp.float64),
                     A((steps,), jnp.int64), A((), jnp.int64), func="rate")
        text = c.as_text()
        assert " while(" not in text
        assert " gather(" not in text
        assert (c.memory_analysis().temp_size_in_bytes
                < series * points * steps)

    def test_group_reduce(self, one_chip):
        """The panel's shape and the node's (S, T): the host owns the
        permutation, so no sort is compiled for the chip; the scan runs
        over S rows with T lanes beside it (its compile seconds are the
        figure PERF.md cites)."""
        from m3_tpu.query import device_fns

        # ... and prom.fleet_quantile's: 100,000 bucket series in row
        # blocks (the padding a group of its own) summed by (job, le)
        for rows, groups in ((400, 10), (S, 1024),
                             (-(-100_000 // _BLOCK) * _BLOCK, 160)):
            c = _compile(device_fns._segment_reduce_kernel, one_chip,
                         A((rows, T), jnp.float64), A((rows,), jnp.int32),
                         A((rows,), jnp.bool_), A((groups,), jnp.int32),
                         A((groups,), jnp.bool_), func="sum")
            text = c.as_text()
            assert "sort(" not in text[text.index("ENTRY"):]

    def test_histogram_quantile(self, one_chip):
        from m3_tpu.query import device_fns

        G, B = 16, 10
        _compile(device_fns._histogram_quantile_kernel, one_chip,
                 A((G * B, T), jnp.float64), A((G, B), jnp.int32),
                 A((G,), jnp.int32), A((G, B), jnp.float64),
                 A((), jnp.float64))
