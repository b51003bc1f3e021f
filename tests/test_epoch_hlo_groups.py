"""scripts/epoch_hlo_groups.py: the reading of a compiled epoch program's
op groups, on a hand-written HLO module (the real compile takes half a
minute and is the script's own to run)."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def groups():
    spec = importlib.util.spec_from_file_location(
        "epoch_hlo_groups", os.path.join(ROOT, "scripts",
                                         "epoch_hlo_groups.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


HLO = """HloModule jit_epoch

%fused_computation.7 (param_0.1: bf16[1024,768], param_1.2: bf16[768,3072]) -> bf16[1024,3072] {
  %param_0.1 = bf16[1024,768]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = bf16[768,3072]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.3 = bf16[1024,3072]{1,0:T(8,128)(2,1)} convolution(%param_0.1, %param_1.2), dim_labels=bf_io->bf
}

%cond.1 (arg.1: (s32[], bf16[1024,768])) -> pred[] {
  %arg.1 = (s32[]{:T(128)}, bf16[1024,768]{1,0}) parameter(0)
  %constant.9 = s32[]{:T(128)} constant(12)
  %get-tuple-element.1 = s32[]{:T(128)} get-tuple-element(%arg.1), index=0
  ROOT %lt.1 = pred[]{:T(512)} compare(%get-tuple-element.1, %constant.9), direction=LT
}

%body.1 (arg.2: (s32[], bf16[1024,768])) -> (s32[], bf16[1024,768]) {
  %arg.2 = (s32[]{:T(128)}, /*index=1*/bf16[1024,768]{1,0:T(8,128)(2,1)}) parameter(0)
  %x.1 = bf16[1024,768]{1,0:T(8,128)(2,1)} get-tuple-element(%arg.2), index=1
  %w.1 = bf16[768,3072]{1,0:T(8,128)(2,1)} constant({...})
  %convolution_add_fusion.12 = bf16[1024,3072]{1,0:T(8,128)(2,1)} fusion(%x.1, %w.1), kind=kOutput, calls=%fused_computation.7
  %copy-start.4 = (bf16[1024,768]{1,0}, bf16[1024,768]{1,0:S(1)}, u32[]{:S(2)}) copy-start(%x.1)
  %copy-done.4 = bf16[1024,768]{1,0:S(1)} copy-done(%copy-start.4)
  %i.1 = s32[]{:T(128)} get-tuple-element(%arg.2), index=0
  ROOT %tuple.3 = (s32[]{:T(128)}, bf16[1024,768]{1,0}) tuple(%i.1, %copy-done.4)
}

ENTRY %main.5 (p.1: bf16[1024,768]) -> bf16[1024,768] {
  %p.1 = bf16[1024,768]{1,0:T(8,128)(2,1)} parameter(0)
  %zero.1 = s32[]{:T(128)} constant(0)
  %tuple.1 = (s32[]{:T(128)}, bf16[1024,768]{1,0}) tuple(%zero.1, %p.1)
  %while.2 = (s32[]{:T(128)}, /*index=1*/bf16[1024,768]{1,0:T(8,128)(2,1)}) while(%tuple.1), condition=%cond.1, body=%body.1
  %out.1 = bf16[1024,768]{1,0} get-tuple-element(%while.2), index=1
  ROOT %copy.8 = bf16[1024,768]{1,0:T(8,128)(2,1)} copy(%out.1)
}
"""


def test_groups_follow_the_benchmarks_rule(groups):
    """Restated, not imported: must stay what the ledger's breakdown uses."""
    from benchmark.lib import trace_reduce
    for name in ("%fusion.123", "select_add_fusion.7", "copy",
                 "%convolution.269.clone.3", "transpose(jvp())", "%while.4",
                 "slice-done.12"):
        assert groups.op_group(name) == trace_reduce.op_group(name), name


def test_walk_counts_flops_bytes_and_trip_counts(groups):
    comps = groups.parse_computations(HLO)
    rows = []
    groups.walk(comps, groups.result_types(comps), "__entry__", 1, rows)
    by_group = {g: (runs, flops, nbytes) for g, runs, flops, nbytes in rows}
    # the scan's length comes from its condition; its body's work runs 12×
    matmul = 2.0 * 1024 * 3072 * 768
    io = 2 * (1024 * 768 + 768 * 3072 + 1024 * 3072)
    assert by_group["convolution_add_fusion"] == (12, matmul, io)
    # an asynchronous copy is charged once, at its -done: read + write
    assert by_group["copy-done"] == (12, 0.0, 2 * 2 * 1024 * 768)
    assert "copy-start" not in by_group and "while" not in by_group
    assert by_group["copy"] == (1, 0.0, 2 * 2 * 1024 * 768)
    assert set(by_group) == {"convolution_add_fusion", "copy-done", "copy"}


@pytest.mark.parametrize("touching,want", [
    ([[1024, 3072]], {"convolution_add_fusion"}),
    ([[768, 3072], [4, 4]], {"convolution_add_fusion"}),
    ([[1024, 768]], {"convolution_add_fusion", "copy-done", "copy"}),
    ([[768, 1024]], set())],
    ids=["a_result", "an_operand_of_several", "every_instruction", "none"])
def test_walk_keeps_the_instructions_that_touch_a_shape(groups, touching,
                                                        want):
    """``--touching``: an instruction counts when an operand or a result has
    one of the shapes, whatever its type; trip counts still multiply in."""
    comps = groups.parse_computations(HLO)
    rows = []
    groups.walk(comps, groups.result_types(comps), "__entry__", 1, rows,
                touching)
    assert {g for g, *_ in rows} == want
    assert all(runs == (1 if g == "copy" else 12) for g, runs, *_ in rows)


@pytest.mark.parametrize("shapes,want", [
    (["1024,3072", "768,3072"], {"convolution_add_fusion": (1, 12)}),
    (["1024,768", "768,1024", "4,4"],
     {"convolution_add_fusion": (1, 12), "copy-done": (1, 12),
      "copy": (1, 1)}),
    (["768,1024", "3072,1024"], {})],
    ids=["two_shapes_of_one_instruction", "every_instruction", "none"])
def test_a_kept_text_is_counted_touching_several_shapes(groups, tmp_path,
                                                        monkeypatch, capsys,
                                                        shapes, want):
    """The command as PR 49 used it on the looped cell's program:
    ``--compiled <kept text> --touching a --touching b …`` compiles nothing,
    says which shapes it kept to, lists the groups that touch any of them
    (instructions, runs with the trip counts in) and a total that is 0 where
    nothing does."""
    kept = tmp_path / "epoch.hlo"
    kept.write_text(HLO)
    argv = ["epoch_hlo_groups.py",
            os.path.join(ROOT, "benchmark", "configs",
                         "gpt2-124m-nanogpt.json"), "--compiled", str(kept)]
    for dims in shapes:
        argv += ["--touching", dims]
    monkeypatch.setattr("sys.argv", argv)
    monkeypatch.setattr(groups, "compile_epoch", lambda cfg: pytest.fail(
        "a kept text is counted, not compiled"))
    groups.main()
    lines = capsys.readouterr().out.splitlines()
    assert lines[0] == "instructions touching " + " ".join(shapes) + " only"
    rows = {line.split()[0]: tuple(int(n) for n in line.split()[1:3])
            for line in lines[3:]}
    total = rows.pop("total")
    assert rows == want
    assert total == tuple(sum(r[i] for r in want.values()) for i in (0, 1))


@pytest.mark.parametrize("name", [
    "gpt2-124m-nanogpt", "ouro-2.6b-loop4-6l", "laguna-s-2.1-ep32-5l",
    "xing4.0-29b-a4b-ep8-5l"])
def test_any_training_configuration_names_its_preset(groups, name):
    """The script builds the model of whichever benchmark configuration it
    is given from the reference's ``PRESET`` / ``preset_args``."""
    import json
    from penroz_tpu.models import presets
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json"),
              encoding="utf-8") as fh:
        cfg = json.load(fh)
    layers = groups.preset_layers(cfg)
    assert "embedding" in json.dumps(layers[0]) and "train" in cfg
    assert "softmaxlast" in layers[-1]
    if "parameters_held" in cfg:
        assert presets.param_count(layers) == cfg["parameters_held"]
