"""The scope join (``harness.scopes``) on synthetic traces and on the
traces recorded on a v5e: each op goes to the innermost ``db.*`` scope of
its instruction in the program that ran it, idle gaps to the innermost
host span.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q"""
import os

import pytest

from harness import scopes, trace

TRACES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traces")


def meta(op_name):
    return f', metadata={{op_name="{op_name}"}}'


HLO = "\n".join([
    "HloModule jit_step, is_scheduled=true",
    "",
    "%fused_computation.1 (param_0: f32[4]) -> f32[4] {",
    "  %param_0 = f32[4]{0} parameter(0)",
    "  %exp.1 = f32[4]{0} exponential(%param_0)"
    + meta("jit(step)/jvp(db.noise)/exp"),
    "  ROOT %mul.2 = f32[4]{0} multiply(%exp.1, %exp.1)"
    + meta("jit(step)/transpose(jvp(db.layers))/while/body/db.mlp/mul"),
    "}",
    "",
    "%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {",
    "  %param_0.1 = f32[4]{0} parameter(0)",
    "  ROOT %bitcast.3 = f32[4]{0} bitcast(%param_0.1)",
    "}",
    "",
    "ENTRY %main.9 (p: f32[4]) -> f32[4] {",
    "  %p = f32[4]{0} parameter(0)",
    "  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, "
    "calls=%fused_computation.1",
    "  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, "
    "calls=%fused_computation.2" + meta("jit(step)/db.optimizer/add"),
    "  %dot.4 = f32[4]{0} dot(%fusion.2, %p)"
    + meta("jit(step)/jvp(db.layers)/while/body/db.attn/jit(flash)/"
           "flash_fwd/dot_general"),
    "  ROOT %copy.5 = f32[4]{0} copy(%dot.4)",
    "}", ""])


def test_op_names_resolve_fusions_to_their_root():
    m = scopes.op_names(HLO)
    assert scopes.scope_of(m["fusion.1"]) == "db.mlp"        # the root's
    assert scopes.scope_of(m["fusion.2"]) == "db.optimizer"  # its own
    assert scopes.scope_of(m["dot.4"]) == "db.attn"          # innermost
    assert "copy.5" not in m and "p" not in m


def test_scope_of():
    assert scopes.scope_of("jit(step)/transpose(jvp(db.readout_ce))/x") \
        == "db.readout_ce"
    path = "jit(s)/jvp(db.probe)/while/body/db.layers/db.attn/dot_general"
    assert scopes.scope_path(path) == "db.probe/db.layers/db.attn"
    assert scopes.scope_of(scopes.scope_path(path)) == "db.attn"
    assert scopes.outer_scope_of(path) == "db.probe"
    assert scopes.scope_of("jit(step)/jvp(while)/dbx.attn/add") is None
    assert scopes.scope_of(None) is None


def _events():
    """Two step programs on one device, 0..100 and 200..300 ns, a gap
    100..200 under the program's db.guard_sync inside bench.step."""
    ops = {0: [(0, 40, "%fusion.1 = f32[4] fusion(%p)"),
               (40, 90, "%dot.4 = f32[4] dot(%a, %b)"),
               (90, 100, "%copy.5 = f32[4] copy(%dot.4)"),
               (0, 100, "%while.7 = (f32[4]) while(%t)"),
               (200, 260, "%dot.4 = f32[4] dot(%a, %b)"),
               (260, 300, "%fusion.2 = f32[4] fusion(%p)")]}
    mods = {0: [(0, 100, "jit_step(111)"), (200, 300, "jit_step(222)")]}
    spans = {trace.WINDOW_SPAN: [(0, 300)],
             "bench.step": [(95, 210)],
             "db.guard_sync": [(100, 190)]}
    return ops, mods, spans


def test_join_by_fingerprint_and_gaps_by_innermost_span():
    ops, mods, spans = _events()
    maps = {"jit_step(111)": scopes.op_names(HLO),
            "jit_step(222)": {"dot.4": "db.mlp", "fusion.2": "db.psum"}}
    r = scopes.split(ops, mods, spans, maps)
    assert r.same_clock and r.keyed_by == {"jit_step": "fingerprint"}
    s = {k: round(v * 1e9) for k, v in r.seconds.items()}
    # the while loop is left out; copy.5 has no scope
    assert s == {"db.mlp": 40 + 60, "db.attn": 50, "db.psum": 40,
                 scopes.UNSCOPED: 10}
    assert r.programs == {"jit_step": 2}
    o = {k: round(v * 1e9) for k, v in r.outer["jit_step"].items()}
    assert o == {"db.layers": 40 + 50, "db.mlp": 60, "db.psum": 40,
                 scopes.UNSCOPED: 10}
    g = {k: round(v * 1e9) for k, v in r.gaps.items()}
    assert g == {"db.guard_sync": 100}
    assert abs(r.busy_s - 200e-9) < 1e-15 and abs(r.window_s - 300e-9) < 1e-15


def test_join_by_name_counts_disagreement_as_unscoped():
    ops, mods, spans = _events()
    maps = {"jit_step(7)": {"fusion.1": "db.noise", "dot.4": "db.attn"},
            "jit_step(8)": {"fusion.1": "db.noise", "dot.4": "db.mlp"}}
    r = scopes.split(ops, mods, spans, maps)
    assert r.keyed_by == {"jit_step": "name"}
    s = {k: round(v * 1e9) for k, v in r.seconds.items()}
    assert s == {"db.noise": 40, scopes.UNSCOPED: 50 + 10 + 60 + 40}


def test_gaps_need_one_clock():
    ops, mods, spans = _events()
    spans[trace.WINDOW_SPAN] = [(10_000, 20_000)]     # another clock
    r = scopes.split(ops, mods, spans, {})
    assert not r.same_clock
    assert set(r.gaps) == {scopes.NO_SPAN}
    assert set(r.seconds) == {scopes.UNSCOPED}      # no maps: no scopes


def test_save_and_load_keep_only_scopes(tmp_path):
    maps = {"jit_step(111)": scopes.op_names(HLO)}
    scopes.save(str(tmp_path), maps)
    back = scopes.load(str(tmp_path))
    assert back == {"jit_step(111)": {"fusion.1": "db.layers/db.mlp",
                                      "fusion.2": "db.optimizer",
                                      "dot.4": "db.layers/db.attn"}}
    assert scopes.load(str(tmp_path / "none")) == {}


def recorded():
    return sorted(os.path.join(TRACES, d) for d in os.listdir(TRACES)
                  if os.path.isdir(os.path.join(TRACES, d))
                  and not d.startswith("_"))


@pytest.mark.parametrize("path", recorded(), ids=os.path.basename)
def test_recorded_traces_split_like_the_reducer(path):
    """Busy time and, where no db.* span is open, idle gaps read as
    ``harness.trace.reduce`` reads them; where an op-name map was saved,
    under 5% of busy time is unscoped."""
    r = trace.reduce(path)
    s = scopes.reduce(path)
    assert s.busy_s == r.busy_s and s.window_s == r.window_s
    if not any(k.startswith("db.") for k in s.gaps):
        assert s.gaps == r.gaps
    total = sum(s.seconds.values())
    assert abs(total - sum(v for k, v in r.ops.items()
                           if k not in trace.CONTAINERS)) < 1e-9
    if scopes.load(path):
        assert s.scope_seconds(scopes.UNSCOPED) < 0.05 * s.busy_s * \
            s.n_devices, s.seconds


def test_programs_of_one_name_are_all_kept():
    class Exe:
        def __init__(self, text):
            self.text = text

        def runtime_executable(self):
            return self

        def hlo_modules(self):
            return [type("M", (), {"name": "jit_step"})]

        def as_text(self):
            return self.text
    maps = scopes.maps_of([Exe(HLO), Exe(HLO.replace("db.attn", "db.mlp"))])
    assert sorted(maps) == ["jit_step", "jit_step[1]"]
    ops, mods, spans = _events()
    r = scopes.split(ops, mods, spans, maps)
    s = {k: round(v * 1e9) for k, v in r.seconds.items()}
    # dot.4 differs between the two: unscoped
    assert s == {"db.mlp": 40, "db.optimizer": 40, scopes.UNSCOPED: 120}


def test_a_fused_product_names_its_fusion():
    """A weight gradient written into the layer scan's stacked buffer: the
    fusion's root is the scan's dynamic-update-slice, its cost the dot."""
    hlo = "\n".join([
        "%fused_computation.7 (p0: f32[8,8], p1: f32[3,8,8], p2: s32[]) "
        "-> f32[3,8,8] {",
        "  %p0 = f32[8,8]{1,0} parameter(0)",
        "  %dot.1 = f32[8,8]{1,0} convolution(%p0, %p0), "
        "dim_labels=bf_io->bf"
        + meta("jit(step)/transpose(jvp(db.layers))/while/body/db.mlp/"
               "dot_general"),
        "  %bitcast.2 = f32[1,8,8]{2,1,0} bitcast(%dot.1)",
        "  ROOT %dus.3 = f32[3,8,8]{2,1,0} dynamic-update-slice(%p1, "
        "%bitcast.2, %p2)"
        + meta("jit(step)/transpose(jvp(db.layers))/while/body/"
               "dynamic_update_slice"),
        "}",
        "",
        "ENTRY %main.1 (a: f32[8,8], b: f32[3,8,8], i: s32[]) -> "
        "f32[3,8,8] {",
        "  ROOT %bitcast_dynamic-update-slice_fusion.4 = f32[3,8,8]{2,1,0} "
        "fusion(%a, %b, %i), kind=kOutput, calls=%fused_computation.7",
        "}", ""])
    m = scopes.op_names(hlo)
    assert scopes.scope_path(m["bitcast_dynamic-update-slice_fusion.4"]) \
        == "db.layers/db.mlp"
