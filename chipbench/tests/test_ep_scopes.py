"""The expert exchange's readers (``ep_scopes.py``): a collective known
by its HLO opcode in a compiled program's own proto (an ``all-gather``
and a ``reduce-scatter`` under ``moe_exchange`` compiled here for four
host devices, read through the wire-format reader), its device time and
the part no other operation covers on a table worked out by hand, and a
program without the scope (the parent commit's, or a step with no ``ep``
axis), which reads nothing and raises nothing."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import ep_scopes, harness, step_parts, trace

TINY = Path(__file__).parent / "tiny"
REPO = Path(__file__).resolve().parents[2]
BODY = "jit(train_epoch)/while/body/closed_call/"
READERS = ("moe_exchange_ms", "moe_exchange_exposed_ms",
           "step_unnamed_ep_pct")

_COMPILED = """
import jax, jax.numpy as jnp, numpy as np, sys
from jax.sharding import Mesh, PartitionSpec as P
sys.path.insert(0, {repo!r})
from chipbench import ep_scopes
mesh = Mesh(np.array(jax.devices()), ("ep",))
def body(x):
    with jax.named_scope("moe_exchange"):
        g = jax.lax.all_gather(x, "ep", axis=0, tiled=True)
    y = jnp.tanh(g) * 2.0
    with jax.named_scope("moe_exchange"):
        out = jax.lax.psum_scatter(y, "ep", scatter_dimension=0, tiled=True)
    return jax.lax.psum(out, "ep")
fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("ep"), out_specs=P(),
                           check_vma=False))
module = fn.lower(jnp.zeros((8, 4))).compile().runtime_executable() \\
    .hlo_modules()[0].as_serialized_hlo_module_proto()
size, head = len(module), bytearray([0x0a])     # HloProto.hlo_module = 1
while True:
    head.append((size & 0x7F) | (0x80 if size > 0x7F else 0))
    size >>= 7
    if not size:
        break
found = ep_scopes.opcodes(memoryview(bytes(head) + module))
ours = sorted(n for n, pairs in found.items() if ep_scopes.is_exchange(pairs))
kinds = sorted({{pairs[0][0] for pairs in found.values()}})
print("OURS", ours)
print("KINDS", kinds)
"""


def test_a_compiled_programs_collectives_are_known_by_opcode():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS":
           "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, "-c", _COMPILED.format(repo=str(REPO))], env=env,
        capture_output=True, text=True, check=True).stdout
    ours = eval(out.split("OURS ", 1)[1].split("\n", 1)[0])
    kinds = eval(out.split("KINDS ", 1)[1].split("\n", 1)[0])
    # the gather and the scatter under the scope, not the psum beside them
    assert len(ours) == 2, out
    assert any("all-reduce" in k for k in kinds), kinds


def test_the_scope_under_transformations():
    yes = ep_scopes.carries_scope
    assert yes(BODY + "jvp(forward_loss)/SparseMoELM/layer_0/moe/"
               "moe_exchange/all_gather")
    assert yes(BODY + "transpose(jvp(forward_loss))/SparseMoELM/checkpoint/"
               "layer_3/moe/transpose(jvp(moe_exchange))/reduce_scatter")
    assert not yes(BODY + "jvp(forward_loss)/layer_0/moe/moe_route/sort")
    assert not yes("") and not yes(None)
    gather = [("fusion", ""), ("all-gather", BODY + "moe/moe_exchange/x")]
    assert ep_scopes.is_exchange(gather)
    assert ep_scopes.is_exchange([("reduce-scatter-start",
                                   BODY + "jvp(moe_exchange)/y")])
    # another scope's collective, and the scope's own arithmetic
    assert not ep_scopes.is_exchange([("all-reduce",
                                       BODY + "moe_exchange/psum")])
    assert not ep_scopes.is_exchange([("all-gather", BODY + "grad_allreduce")])
    assert not ep_scopes.is_exchange([("add", BODY + "moe_exchange/add")])


def _ctx(by_name, ops, async_ops, monkeypatch, tmp_path):
    """A reader's context over one chip's events (name, start, duration in
    ns), two executions of a 2-step program."""
    from chipbench import trace_scopes

    cell = harness.resolve_cell("tiny_fit_sync_ep",
                                TINY / "BENCHMARK_ep.json", TINY)
    table = {"/device:TPU:0": {
        trace.OPS_LINE: ops, trace.ASYNC_LINE: async_ops,
        trace.MODULES_LINE: [("jit_train_epoch(1)", 0.0, 1000.0),
                             ("jit_train_epoch(1)", 1000.0, 1000.0)]}}
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace, "newest_xplane",
                        lambda _dir: tmp_path / "t.xplane.pb")
    monkeypatch.setattr(ep_scopes, "program_opcodes",
                        lambda _bytes, _program: by_name)
    monkeypatch.setattr(
        trace_scopes, "program_instructions", lambda _bytes, _program: {
            name: (pairs[0][1], [p[1] for p in pairs[1:]])
            for name, pairs in by_name.items()})
    return {"cell": cell, "trace": table,
            "summary": {"window": (0.0, 2000.0), "busy_s": 2e-6},
            "inputs": {"steps_per_call": 2, "examples_per_step": 4,
                       "n_chips": 1},
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _reader(name):
    return harness.load_module("layer_metrics", name, TINY).read


def test_the_readers_on_a_table_worked_out_by_hand(monkeypatch, tmp_path):
    fwd = BODY + "jvp(forward_loss)/SparseMoELM/layer_0/moe/"
    by_name = {
        # an asynchronous gather: a fusion around the collective
        "fusion.7": [("fusion", ""), ("all-gather",
                                      fwd + "moe_exchange/all_gather")],
        "reduce-scatter.3": [("reduce-scatter",
                              fwd + "moe_exchange/reduce_scatter")],
        "add.9": [("add", fwd + "moe_exchange/add")],
        "fusion.1": [("fusion", fwd + "moe_route/sort")],
        "fusion.2": [("fusion", fwd + "moe_experts/pallas_call")],
        "fusion.3": [("fusion", BODY + "jvp(forward_loss)/SparseMoELM/x")],
    }
    ops = [("%fusion.1 = f32[] fusion()", 0.0, 100.0),
           ("%fusion.7 = f32[] fusion()", 100.0, 10.0),      # the start
           ("%fusion.2 = f32[] fusion()", 110.0, 190.0),     # hides 190
           ("%reduce-scatter.3 = f32[] reduce-scatter()", 400.0, 100.0),
           ("%add.9 = f32[] add()", 500.0, 20.0),
           ("%fusion.3 = f32[] fusion()", 520.0, 80.0)]
    # the gather in flight from 100 to 400: 10 its own, 190 under the
    # experts' kernel, 100 under nothing
    async_ops = [("%fusion.7 = f32[] fusion()", 100.0, 300.0)]
    ctx = _ctx(by_name, ops, async_ops, monkeypatch, tmp_path)
    steps, ms = 4, 1e-6
    assert _reader("moe_exchange_ms")(ctx) == pytest.approx(
        (300.0 + 100.0) * ms / steps)
    assert _reader("moe_exchange_exposed_ms")(ctx) == pytest.approx(
        (300.0 - 190.0 + 100.0) * ms / steps)
    # unnamed before: the scatter, the add and fusion.3 (100 + 20 + 80; the
    # gather's fusion has no ``op_name`` of its own and no phase); the
    # scope takes the scatter and the add out
    assert step_parts.unnamed_pct(ctx) == pytest.approx(100.0 * 200.0 / 2000.0)
    assert _reader("step_unnamed_ep_pct")(ctx) == pytest.approx(
        100.0 * 80.0 / 2000.0)


def test_a_program_without_the_scope_reads_nothing(monkeypatch, tmp_path):
    fwd = BODY + "jvp(forward_loss)/SparseMoELM/layer_0/moe/"
    by_name = {"fusion.1": [("fusion", fwd + "moe_route/sort")],
               "all-reduce.2": [("all-reduce", BODY + "grad_allreduce/psum")]}
    ops = [("%fusion.1 = f32[] fusion()", 0.0, 100.0),
           ("%all-reduce.2 = f32[] all-reduce()", 100.0, 50.0)]
    ctx = _ctx(by_name, ops, [], monkeypatch, tmp_path)
    for name in READERS:
        assert _reader(name)(ctx) is None
    for name in READERS:  # and with no trace at all
        assert _reader(name)({**ctx, "trace": None}) is None
