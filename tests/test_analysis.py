"""Static contract checker: every rule catches its seeded violation, and
the clean repo produces zero findings.

The seeded violations mirror the acceptance list: an injected fp32 upcast
in a packed cell (PF102), a hand-rolled out-of-contract pspec (SC202), a
cell arg that forks the compile cache (RC301/RC303), and an over-budget
collective measured from real HLO accounting (BC501).
"""
import importlib.util
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis.budgets import (HEADROOM, budget_entry, check_budget,
                                    load_budgets)
from repro.analysis.findings import (Finding, filter_suppressed,
                                     parse_pragmas)
from repro.analysis.lint import lint_source, lint_tree
from repro.analysis.precision import check_precision
from repro.analysis.recompile import (check_fingerprint,
                                      check_key_collisions,
                                      check_trace_determinism)
from repro.analysis.shardspec import (check_celldef_specs,
                                      check_shard_map_reductions,
                                      check_spec_tree)
from repro.dist.mesh import host_mesh, use_mesh
from repro.serve.cells import ServeCellDef

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(findings):
    return [f.code for f in findings]


def _celldef(**kw):
    d = dict(arch="t", shape="s", kind="score", batch=4,
             step_fn=lambda x: x * 2.0,
             bound=(), bound_pspecs=(),
             request_specs=(jax.ShapeDtypeStruct((4, 3), jnp.float32),),
             request_pspecs=(P(None, None),),
             out_pspecs=P(None, None), meta={"kind": "score"}, static=None)
    d.update(kw)
    return ServeCellDef(**d)


# -- precision flow (PF1xx) -------------------------------------------------

def test_pf101_float64_output():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2.0)(
            jnp.ones((4,), jnp.float32))
    assert "PF101" in _codes(check_precision(jaxpr, "seeded"))


def test_pf102_injected_upcast_in_packed_cell(tmp_path):
    """The acceptance seed: an inline int8->f32 dequant written in a module
    under a ``repro/`` path (so the user frame is attributable) but outside
    the sanctioned quantizer/packing call sites."""
    pkg = tmp_path / "repro_seeded" / "repro"
    pkg.mkdir(parents=True)
    bad = pkg / "bad_cell.py"
    bad.write_text(textwrap.dedent("""\
        import jax.numpy as jnp

        def bad_lookup(table, alpha, ids):
            codes = jnp.take(table, ids, axis=0)
            return codes.astype(jnp.float32) * alpha   # inline dequant
    """))
    spec = importlib.util.spec_from_file_location("repro_bad_cell", bad)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    table = jnp.zeros((16, 8), jnp.int8)
    alpha = jnp.float32(0.1)
    ids = jnp.zeros((4,), jnp.int32)
    jaxpr = jax.make_jaxpr(mod.bad_lookup)(table, alpha, ids)
    found = check_precision(jaxpr, "seeded-packed", packed=True)
    pf102 = [f for f in found if f.code == "PF102"]
    assert pf102 and pf102[0].file.endswith("repro/bad_cell.py")
    assert pf102[0].line == 5


def test_pf102_sanctioned_dequant_is_clean():
    """The same computation routed through core.quantizer attributes its
    convert to the sanctioned module and passes."""
    from repro.core.quantizer import dequantize_codes
    codes = jnp.zeros((4, 8), jnp.int8)
    alpha = jnp.float32(0.1)
    jaxpr = jax.make_jaxpr(
        lambda c, a: dequantize_codes(c, a, jnp.float32(0.0)))(codes, alpha)
    assert _codes(check_precision(jaxpr, "clean", packed=True)) == []


def test_pf102_int32_only_narrow_for_packed_cells():
    """int32 index math converts are legal in unpacked cells and flagged in
    packed ones — but only when the frame is inside repro/ (this test file
    is outside, so both pass; frame attribution is what PF102 keys on)."""
    jaxpr = jax.make_jaxpr(lambda i: i.astype(jnp.float32))(
        jnp.zeros((4,), jnp.int32))
    assert _codes(check_precision(jaxpr, "x", packed=False)) == []
    assert _codes(check_precision(jaxpr, "x", packed=True)) == []


def test_pf103_packed_words_into_float():
    jaxpr = jax.make_jaxpr(lambda w: w.astype(jnp.float32))(
        jnp.zeros((4,), jnp.uint32))
    assert "PF103" in _codes(check_precision(jaxpr, "seeded"))


def test_pf104_int8_arithmetic():
    jaxpr = jax.make_jaxpr(lambda a, b: a * b)(
        jnp.zeros((4,), jnp.int8), jnp.zeros((4,), jnp.int8))
    assert "PF104" in _codes(check_precision(jaxpr, "seeded"))


# -- sharding contract (SC2xx) ----------------------------------------------

def test_sc201_unknown_axis():
    found = check_spec_tree(P("rows"), "seeded", role="out")
    assert _codes(found) == ["SC201"]


def test_sc202_out_of_contract_pspec():
    """The acceptance seed: a hand-rolled pspec whose axis pair is not a
    registered AXIS_GROUPS entry (wrong order changes the row-major shard
    index)."""
    celldef = _celldef(out_pspecs=P(("model", "data"), None))
    found = check_celldef_specs(celldef)
    assert "SC202" in _codes(found)
    # the registered order is fine
    assert check_celldef_specs(
        _celldef(out_pspecs=P(("data", "model"), None))) == []


def test_sc202_nested_spec_trees():
    found = check_spec_tree({"k": P(None), "v": P(("model", "pod"))},
                            "seeded", role="bound[0]")
    assert _codes(found) == ["SC202"]


def test_sc204_shard_map_partial_without_psum():
    mesh = host_mesh()

    def partial_body(x):
        return jnp.sum(x, axis=0)          # device-local partial, no merge

    def merged_body(x):
        return jax.lax.psum(jnp.sum(x, axis=0), "model")

    x = jnp.ones((4, 8), jnp.float32)
    with use_mesh(mesh):
        bad = jax.make_jaxpr(jax.shard_map(
            partial_body, mesh=mesh, in_specs=P("model", None),
            out_specs=P(None), check_vma=False))(x)
        good = jax.make_jaxpr(jax.shard_map(
            merged_body, mesh=mesh, in_specs=P("model", None),
            out_specs=P(None), check_vma=False))(x)
    assert _codes(check_shard_map_reductions(bad, "seeded")) == ["SC204"]
    assert check_shard_map_reductions(good, "clean") == []


# -- recompile hazards (RC3xx) ----------------------------------------------

def test_rc301_weak_typed_bound_forks_cache():
    """The acceptance seed: a Python scalar closed into ``bound`` traces
    weak-typed — the first strongly-typed request re-traces the cell."""
    celldef = _celldef(step_fn=lambda s, x: x * s, bound=(3.0,),
                       bound_pspecs=(P(),))
    assert "RC301" in _codes(check_fingerprint(celldef))
    fixed = _celldef(step_fn=lambda s, x: x * s,
                     bound=(jnp.asarray(3.0, jnp.float32),),
                     bound_pspecs=(P(),))
    assert check_fingerprint(fixed) == []


def test_rc302_address_in_fingerprint():
    class Opaque:                               # default __repr__: 0x...
        pass
    celldef = _celldef(static=Opaque())
    assert "RC302" in _codes(check_fingerprint(celldef))


def test_rc303_key_collision_different_signatures():
    a = _celldef()
    b = _celldef(request_specs=(jax.ShapeDtypeStruct((4, 3), jnp.bfloat16),))
    assert a.fingerprint == b.fingerprint       # identical identity fields
    assert _codes(check_key_collisions([a, b])) == ["RC303"]
    assert check_key_collisions([a, a]) == []


def test_rc304_nondeterministic_trace():
    calls = []

    def step(x):
        calls.append(1)
        return x * float(len(calls))            # constant changes per trace

    celldef = _celldef(step_fn=step)
    x = jnp.ones((4,), jnp.float32)
    # the fresh lambda per call defeats make_jaxpr's identity-keyed trace
    # cache, exactly as corpus.trace_cell does
    found = check_trace_determinism(
        celldef, lambda: jax.make_jaxpr(lambda y: step(y))(x))
    assert _codes(found) == ["RC304"]
    assert check_trace_determinism(
        celldef, lambda: jax.make_jaxpr(lambda y: y * 2.0)(x)) == []


# -- collective budgets (BC5xx) ---------------------------------------------

_AR_HLO = """\
HloModule m

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64] parameter(0)
  ROOT %ar = f32[64] all-reduce(%p0), to_apply=%add
}
"""


def test_bc501_over_budget_collective():
    """The acceptance seed: a real all-reduce measured by the HLO
    accounting (64 f32 = 256 bytes) against a 128-byte budget."""
    from repro.launch.hlo_analysis import analyze
    measured = analyze(_AR_HLO)["collectives_per_device"]
    assert measured["total_bytes"] == 256
    assert measured["all-reduce"]["count"] == 1
    found = check_budget("cell", measured, {"cell": {"total_bytes": 128}})
    assert _codes(found) == ["BC501"]
    assert check_budget("cell", measured,
                        {"cell": {"total_bytes": 256}}) == []


def test_bc502_missing_budget_entry():
    found = check_budget("newcell", {"total_bytes": 0.0}, {})
    assert _codes(found) == ["BC502"]


def test_budget_entry_headroom():
    assert budget_entry({"total_bytes": 1000})["total_bytes"] == \
        int(1000 * HEADROOM)


# -- source lint (RL4xx) ----------------------------------------------------

def test_rl401_hand_rolled_pspec():
    src = ("from jax.sharding import PartitionSpec as P\n"
           "x = P('data', None)\n"
           "y = maybe_shard(z, P('model', None))\n"
           "w = P(dp, None)\n")
    found = lint_source(src, "src/repro/serve/foo.py")
    assert _codes(found) == ["RL401"] and found[0].line == 2
    assert lint_source(src, "src/repro/dist/sharding.py") == []


def test_rl402_shard_map_outside_dist():
    src = ("from jax import shard_map\n"
           "f = shard_map(g, mesh=m)\n")
    assert _codes(lint_source(src, "src/repro/serve/foo.py")) == \
        ["RL402", "RL402"]
    assert lint_source(src, "src/repro/dist/shard.py") == []


def test_rl403_host_sync_in_serve():
    src = "import jax\njax.block_until_ready(x)\n"
    assert _codes(lint_source(src, "src/repro/serve/foo.py")) == ["RL403"]
    assert lint_source(src, "src/repro/launch/foo.py") == []


def test_rl404_device_float64_literal():
    src = ("import jax.numpy as jnp\nimport numpy as np\n"
           "a = jnp.zeros((3,), jnp.float64)\n"
           "b = np.zeros((3,), np.float64)\n")   # host-side: legal
    found = lint_source(src, "src/repro/core/foo.py")
    assert _codes(found) == ["RL404"] and found[0].line == 3


def test_rl405_nondeterminism_in_cell_modules():
    src = "import time\nt = time.time()\n"
    assert _codes(lint_source(src, "src/repro/serve/cells.py")) == ["RL405"]
    assert lint_source(src, "src/repro/serve/engine.py") == []


# -- pragma suppression ------------------------------------------------------

def test_parse_pragmas():
    src = ("x = 1  # staticcheck: ignore[PF102, SC202]\n"
           "y = 2  # staticcheck: ignore\n"
           "z = 3\n")
    assert parse_pragmas(src) == {1: {"PF102", "SC202"}, 2: None}


def test_lint_pragma_suppresses_named_rule():
    src = ("import jax\n"
           "jax.block_until_ready(x)  # staticcheck: ignore[RL403]\n"
           "jax.device_get(y)  # staticcheck: ignore[RL401]\n")
    assert _codes(lint_source(src, "src/repro/serve/foo.py")) == ["RL403"]


def test_trace_finding_pragma(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text("deq = codes.astype(f32)  # staticcheck: ignore[PF102]\n")
    hit = Finding("PF102", "m", "cell", file=str(f), line=1)
    miss = Finding("PF104", "m", "cell", file=str(f), line=1)
    assert filter_suppressed([hit, miss]) == [miss]


# -- the clean repo ----------------------------------------------------------

def test_lint_clean_on_repo():
    assert [f.render() for f in lint_tree(REPO_ROOT)] == []


@pytest.fixture(scope="module")
def corpus_engine():
    from repro.analysis.corpus import build_corpus
    return build_corpus()


def test_registered_cells_introspection(corpus_engine):
    cells = corpus_engine.registered_cells()
    names = {reg.celldef.name for reg in cells.values()}
    # every cell kind is represented, lookup companions included
    expected = {"dlrm/serve_p99", "dlrm/serve_p99.lookup", "dlrm/serve_bulk",
                "dlrm/serve_bulk.lookup", "dlrm/tiered_p99",
                "dlrm/tiered_bulk", "lm-tiny/decode", "lm-cb/decode_cb"}
    if jax.device_count() >= 4:  # the a2a comms variants need a real mesh
        expected |= {"dlrm/serve_p99_a2a", "dlrm/tiered_p99_a2a"}
    assert expected == names


def test_clean_corpus_no_findings(corpus_engine):
    """The gate's exit-0 property: the full trace-level pass over the
    standard fleet, against the checked-in budgets, finds nothing."""
    from repro.analysis.runner import check_engine
    rep = check_engine(corpus_engine, budgets=load_budgets())
    assert rep.n_cells == (10 if jax.device_count() >= 4 else 8)
    assert [f.render() for f in rep.findings] == []
    # every corpus cell has a budget line checked in; the a2a cells only
    # compile on a >1-device model axis, so on a 1x1 session their budget
    # lines are present but unexercised
    budgets = load_budgets()
    assert set(rep.measured) <= set(budgets)
    unmeasured = set(budgets) - set(rep.measured)
    if jax.device_count() >= 4:
        assert not unmeasured
    else:
        assert unmeasured <= {"dlrm/serve_p99_a2a@64", "dlrm/tiered_p99_a2a@64"}
