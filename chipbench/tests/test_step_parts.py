"""A traced step as a sum of named parts (``chipbench/step_parts.py``):
the table's scopes under JAX's wrappers, the eight readers of PR 37 on a
table worked out by hand and on recorded cuts of one traced step of each
8k cell (every instruction's ``op_name`` kept, the unnamed ones too), the
parts tiling the step, ``step_unnamed_pct`` rising by a scope's share
when its names are blanked, and a program without the scopes (the parent
commit's, another model's), which reads nothing and raises nothing."""

import json
from pathlib import Path

import pytest

from chipbench import harness, step_parts, trace, trace_scopes

TINY = Path(__file__).parent / "tiny"
DATA = Path(__file__).parent / "data"
BODY = "jit(train_epoch)/shard_map/while/body/closed_call/"
FWD = BODY + "jvp(forward_loss)/"
BWD = BODY + "transpose(jvp(forward_loss))/"
SCOPE_READERS = tuple(step_parts.TABLE)
READERS = (*SCOPE_READERS, "step_stats_ms", "step_unscoped_ms",
           "step_unnamed_pct", "sync_chunk_enqueue_pct")
CELLS_8K = ("keye_vl2_fit_sync_s8k", "sdar_30b_fit_sync_s8k",
            "laguna_xs2_fit_sync_s8k")
CUTS = {"keye_vl2_fit_sync_s8k": "keye_step_parts.json",
        "sdar_30b_fit_sync_s8k": "sdar_step_parts.json",
        "laguna_xs2_fit_sync_s8k": "laguna_step_parts.json"}


def _reader(name):
    return harness.load_module("layer_metrics", name).read


def test_scope_of_an_op_name_under_transformations():
    scope = step_parts.scope_of
    layer = FWD + "jvp(SparseMoELM)/jvp(layer_1)/jvp(attn)/"
    assert scope(layer + "attn._qkv/jvp(attn_qkv)/attn._proj/dot_general") \
        == "attn_qkv"
    assert scope(layer + "attn._qkv/jvp(attn_qk_rope)/mul") == "attn_qk_rope"
    assert scope(BWD + "SparseMoELM/checkpoint/rematted_computation/layer_2/"
                 "attn/attn._out/attn_out/dot_general") == "attn_out"
    assert scope(BWD + "transpose(jvp(SparseMoELM))/transpose(jvp(layer_3))/"
                 "transpose(jvp(attn))/transpose(jvp(attn_gate))/mul") \
        == "attn_gate"
    assert scope(FWD + "SparseMoELM/attn_qk_rope/cos") == "attn_qk_rope"
    assert scope(BWD + "transpose(jvp(SparseMoELM))/transpose(jvp(embed))/"
                 "scatter-add") == "embed"
    assert scope(FWD + "SparseMoELM/layer_0/block_norm/rsqrt") == "block_norm"
    assert scope(FWD + "jvp(loss)/fused_ce_fwd/pallas_call") == "loss"
    # the older files' scopes are named too, innermost first
    assert scope(FWD + "SparseMoELM/lm_head/dot_general") == "lm_head"
    assert scope(layer + "window_attention/transpose") == "window_attention"
    assert scope(FWD + "SparseMoELM/layer_1/add") is None
    assert scope(BODY + "optimizer/mul") is None
    assert scope("") is None and scope(None) is None
    # a reader's own view: the table's scopes alone
    assert scope(layer + "window_attention/x", step_parts.SCOPES) is None
    assert set(step_parts.SCOPES) == {
        "attn_qkv", "attn_out", "attn_gate", "attn_qk_rope", "lm_head",
        "loss", "embed", "block_norm"}


def _program_names(names, monkeypatch, tmp_path):
    """``names`` as what the trace file's metadata plane gives."""
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace, "newest_xplane",
                        lambda _dir: tmp_path / "t.xplane.pb")
    monkeypatch.setattr(trace_scopes, "program_instructions",
                        lambda _bytes, _program: names)


def _ctx(names, events, monkeypatch, tmp_path, busy_s=None):
    """A reader's context over one chip's ``XLA Ops`` events (name,
    start, duration in ns), two executions of a 2-step program."""
    cell = harness.resolve_cell("tiny_fit_sync_hlm",
                                TINY / "BENCHMARK_hlm.json", TINY)
    table = {"/device:TPU:0": {
        trace.OPS_LINE: events,
        trace.MODULES_LINE: [("jit_train_epoch(1)", 0.0, 1000.0),
                             ("jit_train_epoch(1)", 1000.0, 1000.0)]}}
    _program_names(names, monkeypatch, tmp_path)
    window = (0.0, 2000.0)
    return {"cell": cell, "trace": table,
            "summary": {"window": window, "busy_s": (
                trace.busy_seconds(table, window) if busy_s is None
                else busy_s)},
            "inputs": {"steps_per_call": 2, "examples_per_step": 2,
                       "n_chips": 1},
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _blanked(names, scopes):
    """``names`` as a program that does not carry ``scopes`` gives them:
    those components taken off every ``op_name``."""
    strip = lambda op: "/".join(p for p in op.split("/")
                                if step_parts.scope_of(p, scopes) is None)
    return {k: (strip(op), [strip(i) for i in inner])
            for k, (op, inner) in names.items()}


def by_hand():
    attn = FWD + "SparseMoELM/layer_1/attn/"
    names = {
        "fusion.1": (attn + "attn._qkv/attn_qkv/dot_general", []),
        "fusion.2": (attn + "attn._qkv/attn_qk_rope/mul",
                     [attn + "attn._qkv/attn_qk_rope/mul",
                      attn + "attn._qkv/attn_qkv/dot_general", ""]),
        "window_attn_fwd.3": (attn + "window_attention/window_attn_fwd/"
                              "pallas_call", []),
        "fusion.4": (attn + "attn_gate/logistic", []),
        "fusion.5": (BWD + "SparseMoELM/layer_1/attn/attn._out/attn_out/"
                     "dot_general", [BODY + "optimizer/mul"]),
        "fusion.6": (FWD + "SparseMoELM/lm_head/dot_general", []),
        "fusion.7": (FWD + "loss/reduce_sum", []),
        "fusion.8": (BWD + "SparseMoELM/embed/scatter-add", []),
        "fusion.9": (FWD + "SparseMoELM/layer_1/block_norm/rsqrt", []),
        "fusion.10": (FWD + "SparseMoELM/layer_1/add",
                      [FWD + "SparseMoELM/layer_1/add",
                       FWD + "SparseMoELM/layer_1/block_norm/mul"]),
        "ragged-dot-none.11": ("ragged-dot-none", []),
        "copy.12": ("", []),
        "fusion.13": (BODY + "step_stats/reduce_sum", []),
        "fusion.14": (BODY + "optimizer/mul", []),
        "while.15": ("jit(train_epoch)/while", []),
        # left nameless by the compiler: all of one scope of the table,
        # of two scopes, of an older file's scope
        "fusion.16": ("", [attn + "attn._qkv/attn_qk_rope/sub", "",
                           attn + "attn._qkv/attn_qk_rope/mul"]),
        "fusion.17": ("", [attn + "attn._qkv/attn_qk_rope/sub",
                           attn + "attn._qkv/attn_qkv/dot_general"]),
        "fusion.18": ("", [attn + "window_attention/transpose"]),
    }
    # four steps in the window; times in ns
    events = [("%while.15 = while(...)", 0.0, 2000.0)] + [
        (f"%{name} = op()", start, dur) for name, start, dur in [
            ("fusion.1", 0.0, 200.0), ("fusion.2", 200.0, 100.0),
            ("window_attn_fwd.3", 300.0, 400.0), ("fusion.4", 700.0, 40.0),
            ("fusion.5", 740.0, 160.0), ("fusion.6", 900.0, 120.0),
            ("fusion.7", 1020.0, 30.0), ("fusion.8", 1050.0, 50.0),
            ("fusion.9", 1100.0, 20.0), ("fusion.10", 1120.0, 80.0),
            ("ragged-dot-none.11", 1200.0, 300.0), ("copy.12", 1500.0, 60.0),
            ("fusion.13", 1560.0, 40.0), ("fusion.14", 1600.0, 100.0),
            ("fusion.16", 1700.0, 24.0), ("fusion.17", 1724.0, 16.0),
            ("fusion.18", 1740.0, 12.0)]]
    return names, events


def test_readers_on_a_table_worked_out_by_hand(monkeypatch, tmp_path):
    names, events = by_hand()
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    ms = lambda ns: ns / 1e6 / 4
    assert _reader("attn_projections_ms")(ctx) == pytest.approx(
        ms(200.0 + 160.0 + 40.0))
    # with the nameless fusion whose operations are all the scope's
    assert _reader("attn_qk_rope_ms")(ctx) == pytest.approx(ms(100.0 + 24.0))
    assert _reader("lm_head_loss_ms")(ctx) == pytest.approx(ms(120.0 + 30.0))
    assert _reader("embed_norms_ms")(ctx) == pytest.approx(ms(50.0 + 20.0))
    assert _reader("step_stats_ms")(ctx) == pytest.approx(ms(40.0))
    # no phase: the copy and the nameless fusions of two scopes or of
    # an older file's; the ragged-dot call is the expert layer's
    assert _reader("step_unscoped_ms")(ctx) == pytest.approx(
        ms(60.0 + 16.0 + 12.0))
    assert trace_scopes.step_ms(ctx, "unscoped") == pytest.approx(
        ms(360.0 + 52.0))
    assert _reader("moe_experts_ms")(ctx) == pytest.approx(ms(300.0))
    assert _reader("attn_window_ms")(ctx) == pytest.approx(ms(400.0))
    # the residual add, of 1,752 ns busy (the while's own 248 are none's)
    assert _reader("step_device_ms")(ctx) == pytest.approx(ms(2000.0))
    assert _reader("step_unnamed_pct")(ctx) == pytest.approx(
        100.0 * 80.0 / 2000.0)
    tile = step_parts.tile(ctx)
    assert tile == {
        "attn_qkv": pytest.approx(ms(200.0)),
        "attn_qk_rope": pytest.approx(ms(124.0)),
        "window_attention": pytest.approx(ms(400.0)),
        "attn_gate": pytest.approx(ms(40.0)),
        "attn_out": pytest.approx(ms(160.0)),
        "lm_head": pytest.approx(ms(120.0)), "loss": pytest.approx(ms(30.0)),
        "embed": pytest.approx(ms(50.0)),
        "block_norm": pytest.approx(ms(20.0)),
        "unnamed": pytest.approx(ms(80.0)),
        "moe_experts": pytest.approx(ms(300.0)),
        "unscoped": pytest.approx(ms(88.0)),
        "step_stats": pytest.approx(ms(40.0)),
        "optimizer": pytest.approx(ms(100.0))}
    assert sum(tile.values()) == pytest.approx(ms(1752.0))
    # a fusion counts whole under its one name; what else it holds
    assert step_parts.mixed_ms(ctx) == {
        "attn_qk_rope": {"ms": pytest.approx(ms(100.0)),
                         "with": {"attn_qkv": pytest.approx(ms(100.0))}},
        "unnamed": {"ms": pytest.approx(ms(80.0)),
                    "with": {"block_norm": pytest.approx(ms(80.0))}},
        "unscoped": {"ms": pytest.approx(ms(16.0 + 12.0)), "with": {
            "attn_qk_rope": pytest.approx(ms(16.0)),
            "attn_qkv": pytest.approx(ms(16.0)),
            "window_attention": pytest.approx(ms(12.0))}}}


def test_a_scope_the_program_lacks_adds_nothing_and_none_reads_nothing(
        monkeypatch, tmp_path):
    names, events = by_hand()
    # no gate: another model's step
    events = [e for e in events if not e[0].startswith("%fusion.4 ")]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    assert _reader("attn_projections_ms")(ctx) == pytest.approx(
        (200.0 + 160.0) / 1e6 / 4)
    # the parent's program: the older scopes alone
    older = _blanked(names, set(step_parts.SCOPES) - {"lm_head"})
    ctx = _ctx(older, events, monkeypatch, tmp_path)
    for name in ("attn_projections_ms", "attn_qk_rope_ms",
                 "embed_norms_ms"):
        assert _reader(name)(ctx) is None
    assert _reader("lm_head_loss_ms")(ctx) == pytest.approx(120.0 / 1e6 / 4)
    assert _reader("step_unnamed_pct")(ctx) == pytest.approx(
        100.0 * (80.0 + 200.0 + 100.0 + 160.0 + 30.0 + 50.0 + 20.0) / 2000.0)
    # the nameless fusions take no scope the program does not carry
    assert _reader("step_unscoped_ms")(ctx) == pytest.approx(
        (60.0 + 52.0) / 1e6 / 4)


def test_a_program_without_a_models_scopes_reads_its_phases_alone(
        monkeypatch, tmp_path):
    """BERT's step: phases, no model scope. The two step metrics read
    what ``trace_scopes`` gives; the scope readers and the unnamed share
    read nothing."""
    names = {"fusion.1": (FWD + "Bert/layer_3/dot_general", []),
             "fusion.2": (BODY + "step_stats/reduce_sum", []),
             "copy.3": ("", [])}
    events = [("%fusion.1 = fusion()", 0.0, 100.0),
              ("%fusion.2 = fusion()", 100.0, 20.0),
              ("%copy.3 = copy()", 120.0, 8.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    assert _reader("step_stats_ms")(ctx) == pytest.approx(20.0 / 1e6 / 4)
    assert _reader("step_unscoped_ms")(ctx) == pytest.approx(8.0 / 1e6 / 4)
    for name in (*SCOPE_READERS, "step_unnamed_pct"):
        assert _reader(name)(ctx) is None
    assert step_parts.tile(ctx) is None and step_parts.mixed_ms(ctx) is None
    # no scope at all (a program from before PR 24), and no trace
    bare = _ctx({"fusion.1": ("", [])}, events, monkeypatch, tmp_path)
    none = {"cell": ctx["cell"], "trace": None, "summary": None,
            "inputs": {}, "peaks": ctx["peaks"]}
    for name in READERS:
        assert _reader(name)(bare) is None
        assert _reader(name)(none) is None


class _Bus:
    def __init__(self, waits):
        self._waits = waits

    def span_waits(self, path):
        assert path == "train/step_chunk"
        return list(self._waits)


def test_the_chunks_enqueue_share_from_the_spans_waits():
    read = _reader("sync_chunk_enqueue_pct")
    spans = [12.0, 2.0, 2.1, 2.0, 1.9]       # the first is set-up
    waits = [0.5, 1.98, 2.09, 1.97, 1.2]     # the last lies past the window
    inputs = {"telemetry": _Bus(waits), "chunk_span_s": spans, "chunks": 3,
              "window_wall_s": 6.2}
    assert read({"inputs": inputs}) == pytest.approx(
        100.0 * (0.02 + 0.01 + 0.03) / 6.2)
    # with the loop's share outside the chunks it is the host's share
    outside = _reader("sync_loop_outside_chunk_pct")({"inputs": inputs})
    assert outside == pytest.approx(100.0 * (1.0 - 6.1 / 6.2))
    # a bus from before the waits, a ring that lost samples, no chunks
    assert read({"inputs": {**inputs, "telemetry": object()}}) is None
    assert read({"inputs": {**inputs, "telemetry": _Bus(waits[1:])}}) is None
    assert read({"inputs": {**inputs, "chunks": 0}}) is None
    assert read({"inputs": {}}) is None


def _cut_ctx(cell_name, monkeypatch, tmp_path, blank=None):
    """The context of a recorded cut: one traced step of the cell on the
    v5e (the builder's chip run, PR 37), every instruction's ``op_name``
    as the trace's metadata plane gave it. ``blank``: a scope whose
    names are taken off, as if the program did not carry it."""
    cut = json.loads((DATA / CUTS[cell_name]).read_text())
    names = {k: (v[0], v[1]) for k, v in cut["names"].items()}
    if blank:
        names = _blanked(names, {blank})
    cell = harness.resolve_cell(cell_name)
    table = {p: {line: [tuple(e) for e in events]
                 for line, events in lines.items()}
             for p, lines in cut["table"].items()}
    _program_names(names, monkeypatch, tmp_path)
    window = tuple(cut["window"])
    return cut, {"cell": cell, "trace": table,
                 "summary": {"window": window,
                             "busy_s": trace.busy_seconds(table, window)},
                 "inputs": {"steps_per_call": cut["steps_per_call"],
                            "examples_per_step": 2, "n_chips": 1},
                 "peaks": harness.load_peaks("TPU v5 lite")}


@pytest.mark.parametrize("cell_name", CELLS_8K)
def test_readers_on_a_recorded_cut_of_a_chip_trace(cell_name, monkeypatch,
                                                   tmp_path):
    cut, ctx = _cut_ctx(cell_name, monkeypatch, tmp_path)
    device = READERS[:-1]  # the cut holds no bus
    got = {m: _reader(m)(ctx) for m in device}
    assert got == pytest.approx(cut["expected"], rel=1e-6)
    assert all(v is not None and v > 0 for v in got.values())
    assert got["step_unnamed_pct"] < 10.0
    # the parts tile the step: every operation under one part, the four
    # reader files' metrics among them, nothing twice
    tile = step_parts.tile(ctx)
    step = _reader("step_device_ms")(ctx)
    assert sum(tile.values()) == pytest.approx(step, rel=0.01)
    older = {m: _reader(m)(ctx) for m in cut["older"]}
    assert older == pytest.approx(cut["older"], rel=1e-6)
    named = sum(got[m] for m in SCOPE_READERS) + sum(older.values())
    rest = sum(tile.get(p, 0.0) for p in ("sample", "grad_allreduce",
                                          "optimizer"))
    assert (named + got["step_unnamed_pct"] / 100.0 * step + rest
            + got["step_stats_ms"] + got["step_unscoped_ms"]
            ) == pytest.approx(step, rel=0.01)
    assert ("attn_gate" in tile) == (cell_name == "laguna_xs2_fit_sync_s8k")


@pytest.mark.parametrize("cell_name", CELLS_8K)
@pytest.mark.parametrize("blank", ["attn_qk_rope", "attn_qkv", "block_norm"])
def test_blanking_a_scope_raises_the_unnamed_share_by_its_share(
        cell_name, blank, monkeypatch, tmp_path):
    """What the scope named inside the two passes becomes unnamed; what
    it named of the fusions the compiler left nameless (the rotation of
    q and k) goes back to the operations under no phase."""
    _cut, ctx = _cut_ctx(cell_name, monkeypatch, tmp_path)
    unnamed, unscoped = (_reader(m)(ctx) for m in (
        "step_unnamed_pct", "step_unscoped_ms"))
    step = _reader("step_device_ms")(ctx)
    in_passes = sum(ms for (phase, scope), ms in step_parts._reduce(
        ctx)["by"].items() if scope == blank and phase != "unscoped")
    nameless = step_parts.tile(ctx)[blank] - in_passes
    _cut, ctx = _cut_ctx(cell_name, monkeypatch, tmp_path, blank=blank)
    assert blank not in step_parts.tile(ctx)
    assert 100.0 * in_passes / step > 0.1
    assert (nameless > 1.0) == (blank == "attn_qk_rope")
    assert _reader("step_unnamed_pct")(ctx) == pytest.approx(
        unnamed + 100.0 * in_passes / step)
    assert _reader("step_unscoped_ms")(ctx) == pytest.approx(
        unscoped + nameless)


def test_the_new_entries_name_readers_that_load_and_their_cells():
    bench = json.loads(harness.BENCHMARK_JSON.read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-8:] == list(READERS)
    cells = [w["name"] for w in bench["workloads"]]
    for name in READERS:
        entry = by_name[name]
        assert callable(_reader(name))
        assert entry["moves"] == "train_rate_sync"
        assert entry["better"] == "lower"
        assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
        want = (cells if name in ("step_stats_ms", "step_unscoped_ms",
                                  "sync_chunk_enqueue_pct")
                else list(CELLS_8K))
        assert entry["workloads"] == want, name
    assert by_name["sync_chunk_enqueue_pct"]["source"] == "program_span"
    assert by_name["sync_chunk_enqueue_pct"]["layer"] == by_name[
        "sync_loop_outside_chunk_pct"]["layer"]
    assert {by_name[m]["layer"] for m in SCOPE_READERS} == {
        "Attention projections",
        "Decoder trunk (embedding, norms, head, loss)"}
    for name in ("step_stats_ms", "step_unscoped_ms", "step_unnamed_pct"):
        assert by_name[name]["layer"] == by_name["step_device_ms"]["layer"]
    # each metric of the table has its file, and no file a row of none
    files = {p.stem for p in (harness.BENCH_DIR / "layer_metrics").glob(
        "*.py")}
    assert set(step_parts.TABLE) <= files
