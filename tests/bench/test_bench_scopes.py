"""The device-scope join of the benchmark (``bench/metrics/_scopes.py``) and
its readers on the CPU: the program's compiled text names its scopes, the
attribution adds up, a same-named operation of another module is not
credited, and a reader with nothing to read reads nothing."""

import types

import pytest

import _tiny  # noqa: F401  (puts bench/ on the import path)
import data
import run

READERS = ("fit_share_pct", "fit_bootstrap_share_pct",
           "fit_split_stats_share_pct", "unscoped_share_pct")


def _scopes():
    return data.load_module(run.ROOT / "bench" / "metrics" / "_scopes.py")


def _reader(name):
    return run.reader(run.ROOT, name)


# A module's text as XLA prints it: a fusion carries its root's op_name.
TEXT = """HloModule jit__episode_segment
%fused_computation.1 (p: f32[8,384]) -> f32[8,384] {
  %p = f32[8,384]{1,0} parameter(0)
  ROOT %multiply.3 = f32[8,384]{1,0} multiply(%p, %p), metadata={op_name="jit(_episode_segment)/lynceus/segment/while/body/vmap(speculate)/vmap(fit)/vmap(bootstrap)/mul"}
}
ENTRY %main {
  %fusion.1 = f32[8,384]{1,0:T(8,128)} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_episode_segment)/lynceus/segment/while/body/vmap(speculate)/vmap(fit)/vmap(bootstrap)/mul" source_file="trees.py" source_line=96}
  %convolution.2 = f32[2,1,28]{2,1,0} convolution(%a, %b), metadata={op_name="jit(_episode_segment)/lynceus/segment/while/body/vmap(speculate)/while/body/closed_call/vmap(fit)/vmap(split_stats)/dot_general"}
  %fusion.3 = (f32[8]{0}, s32[8]{0}) fusion(%c), kind=kInput, metadata={op_name="jit(_episode_segment)/lynceus/segment/while/body/vmap(speculate)/vmap(fit)/vmap(split_choice)/argmax"}
  %select_step.4 = f32[8,1]{1,0} custom-call(%d), custom_call_target="tpu_custom_call", metadata={op_name="jit(_episode_segment)/lynceus/segment/while/body/vmap(speculate)/select_step/select_step"}
  %fusion.5 = f32[8]{0} fusion(%e), kind=kLoop, metadata={op_name="jit(_episode_segment)/lynceus/segment/while/body/vmap(speculate)/div"}
  %fusion.6 = s32[8]{0} fusion(%f), kind=kLoop, metadata={op_name="jit(_episode_segment)/lynceus/segment/while/body/add"}
  %copy.7 = f32[8,384]{1,0} copy(%g)
  ROOT %while.8 = f32[8]{0} while(%h), metadata={op_name="jit(_episode_segment)/while"}
}
"""

# Device self seconds by full instruction text, as bench/xplane.py keeps
# them; fusion.6 is also the name of a host-path program's op of another
# shape, and add.9 of an op in no program of the map.
OPS = {
    "%fusion.1 = f32[8,384]{1,0:T(8,128)} fusion(...)": 5.0,
    "%convolution.2 = f32[2,1,28]{2,1,0} convolution(...)": 3.0,
    "%fusion.3 = (f32[8]{0}, s32[8]{0}) fusion(...)": 1.0,
    "%select_step.4 = f32[8,1]{1,0} custom-call(...), "
    'custom_call_target="tpu_custom_call"': 0.5,
    "%fusion.5 = f32[8]{0} fusion(...)": 0.25,
    "%fusion.6 = s32[8]{0} fusion(...)": 0.125,
    "%fusion.6 = f32[3,3]{1,0} fusion(...)": 0.0625,
    "%copy.7 = f32[8,384]{1,0} copy(...)": 0.03125,
    "%while.8 = f32[8]{0} while(...)": 0.0,
    "%add.9 = f32[]{} add(...)": 0.015625,
}


def _ctx(text, ops=OPS):
    sc = _scopes()
    return {"trace": {"ops": ops, "busy_s": sum(ops.values())},
            "svc": None, "events": [], "scope_seconds": (
                None if text is None
                else sc.attribute(ops, sc.program_map(text))
                if sc.program_map(text) else {})}


def test_scope_of_takes_the_deepest_scope_through_transform_wrappers():
    sc = _scopes()
    path = ("jit(_episode_segment)/lynceus/segment/while/body/"
            "jit(select_next_batched)/vmap(speculate)/while/body/"
            "closed_call/vmap(fit)/vmap(route)/convert_element_type")
    assert sc.scope_of(path) == "fit/route"
    assert sc.scope_of("jit(_episode_segment)/lynceus/segment/while/"
                       "vmap(speculate)/select_step/select_step/eq") \
        == "select_step"
    assert sc.scope_of("jit(_episode_segment)/lynceus/segment/add") \
        == "segment"
    # A part below "fit" counts only inside "fit".
    assert sc.scope_of("jit(f)/lynceus/segment/route/add") == "segment"
    assert sc.scope_of("jit(_where)/select_n") is None


def test_result_shape_takes_layouts_and_tuples():
    sc = _scopes()
    assert sc.result_shape("f32[8,384]{1,0:T(8,128)} fusion(...)") \
        == "f32[8,384]"
    assert sc.result_shape("(f32[8]{0}, /*index=1*/s32[8]{0:T(128)S(1)}) "
                           "custom-call(...)") == "(f32[8], s32[8])"
    assert sc.result_shape("...") is None


def test_attribution_adds_up_to_the_ops_total():
    sc = _scopes()
    pmap = sc.program_map(TEXT)
    # Every instruction with an op_name; the fused computation's root too.
    assert pmap["fusion.1"] == ("fit/bootstrap", "f32[8,384]")
    assert pmap["multiply.3"][0] == "fit/bootstrap"
    assert "copy.7" not in pmap
    assert pmap["while.8"] == (None, "f32[8]")
    sec = sc.attribute(OPS, pmap)
    assert sum(sec.values()) == pytest.approx(sum(OPS.values()))
    assert sec["fit/bootstrap"] == 5.0
    assert sec["fit/split_stats"] == 3.0
    assert sec["fit/split_choice"] == 1.0
    assert sec["select_step"] == 0.5
    assert sec["speculate"] == 0.25
    assert sec["segment"] == 0.125
    # XLA's copy with no op_name, the loop, the other module's fusion.6
    # and the op of no program in the map.
    assert sec["unscoped"] == pytest.approx(0.0625 + 0.03125 + 0.015625)
    ctx = _ctx(TEXT)
    busy = ctx["trace"]["busy_s"]
    assert _reader("fit_share_pct")(ctx) == pytest.approx(100 * 9.0 / busy)
    assert _reader("fit_bootstrap_share_pct")(ctx) == pytest.approx(
        100 * 5.0 / busy)
    assert _reader("fit_split_stats_share_pct")(ctx) == pytest.approx(
        100 * 3.0 / busy)
    shares = [100 * sec[k] / busy for k in sc.SCOPES]
    assert sum(shares) + _reader("unscoped_share_pct")(ctx) \
        == pytest.approx(100.0)


def test_a_same_named_op_of_another_shape_is_unscoped():
    sc = _scopes()
    ops = {"%fusion.6 = f32[3,3]{1,0} fusion(...)": 2.0,
           "%fusion.1 = f32[8,384]{1,0} fusion(...)": 1.0}
    sec = sc.attribute(ops, sc.program_map(TEXT))
    assert sec["segment"] == 0.0 and sec["unscoped"] == 2.0
    assert sec["fit/bootstrap"] == 1.0
    # A trace name without its shape cannot be told apart either.
    sec = sc.attribute({"%fusion.1 = ...": 1.0}, sc.program_map(TEXT))
    assert sec["unscoped"] == 1.0


def test_readers_read_nothing_from_an_empty_map_or_an_unrun_scope():
    for name in READERS:
        assert _reader(name)(_ctx("HloModule m\n")) is None, name
        assert _reader(name)({"trace": None}) is None, name
    # Ops of none of the fit scopes: the fit readers read nothing.
    ops = {"%fusion.5 = f32[8]{0} fusion(...)": 1.0}
    for name in READERS[:3]:
        assert _reader(name)(_ctx(TEXT, ops)) is None, name
    assert _reader("unscoped_share_pct")(_ctx(TEXT, ops)) == 0.0


def test_a_program_without_scopes_has_nothing_attributed():
    """A program that predates the vocabulary (no segment_program_text)
    attributes none of its device time to a scope."""
    ctx = _ctx(None)
    for name in READERS[:3]:
        assert _reader(name)(ctx) == 0.0, name
    assert _reader("unscoped_share_pct")(ctx) == 100.0


def _span(phase, dur):
    return types.SimpleNamespace(kind="span", data={"phase": phase,
                                                    "dur_s": dur})


def test_outcome_rebuild_reads_the_outcome_spans():
    read = _reader("outcome_rebuild_ms")
    events = [_span("harvest", 1.0), _span("outcome", 0.002),
              _span("outcome", 0.004),
              types.SimpleNamespace(kind="harvest", data={})]
    assert read({"events": events}) == pytest.approx(3.0)
    assert read({"events": events[:1]}) is None


def test_vocabulary_is_the_programs():
    from repro.obs import SCOPES
    assert _scopes().SCOPES == SCOPES


@pytest.fixture(scope="module")
def tiny_text(tmp_path_factory):
    """The compiled segment program of the tiny deployment, as the
    benchmark's readers get it."""
    from repro.core import Settings
    from repro.obs import segment_program_text
    from repro.service import ServiceConfig
    root = _tiny.make_root(tmp_path_factory.mktemp("tiny"))
    cfg = data.load_json(root / "bench" / "configs" / "tiny.json")
    jobs = data.program_jobs(data.make_jobs(root, cfg))
    return segment_program_text(jobs, Settings(**cfg["settings"]),
                                ServiceConfig(**cfg["service"]))


def test_segment_program_text_names_the_scopes(tiny_text):
    sc = _scopes()
    pmap = sc.program_map(tiny_text)
    scopes = {s for s, _ in pmap.values()}
    assert {"fit/bootstrap", "fit/split_stats", "fit/split_choice",
            "fit/route", "segment"} <= scopes
    assert scopes & {"select_step", "speculate"}
    assert all(shape for _, shape in pmap.values())
