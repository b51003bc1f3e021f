"""What the op groups of a training trace are, read without a chip.

    JAX_PLATFORMS=cpu python scripts/epoch_hlo_groups.py \\
        [benchmark/configs/gpt2-124m-nanogpt.json] [--touching 4096,14336]

The ledger's ``breakdown`` of a training cell groups device time by HLO
instruction name with the serial number stripped (``fusion``,
``select_add_fusion``, ``copy`` …: ``benchmark/lib/trace_reduce.py::
op_group``).  The TPU compiler is installed in the sandbox and compiles for a
chip that is described, not attached, so the same names can be had from the
compiled program: this compiles the fast epoch program (``with_ratios=
False``, bfloat16 compute, AdamW or whatever the file names) of a benchmark
configuration file (any whose reference names its preset: ``PRESET`` /
``preset_args``) for one chip of a described ``v5e:2x2`` and prints, per
group, how many instructions it holds, how often they run an optimizer step
(``while`` trip counts multiplied in), the FLOPs of the convolutions they
contain (a TPU's matmuls are convolutions), the bytes they read and write
(operands + results of the top-level instruction; a fusion's inside stays
on the chip), and the least time those allow at the chip's peaks — FLOPs ÷
peak or bytes ÷ bandwidth, whichever is larger, per instruction, summed.
Set beside the measured seconds of a group, that says whether the group is
at its bound or hides something (PERF.md §5 has the reading of PR 30).

``--touching <dims>`` (repeatable) keeps the instructions one of whose
operands or results has that shape, whatever its type: what a program spends
on one array, as PR 48 counted the stream state of ``xing4.0-29b-a4b-ep8-5l``
(``--touching 4096,14336 --touching 512,8,4,3584 --touching 1,4096,4,3584``:
the state as the matmul, the compiler's tiles and the model hold it).
``--compiled <file>`` counts a compiled text kept by ``--hlo`` instead of
compiling (minutes for the larger configurations).

Nothing runs and nothing is timed: every number is a count from shapes.
Pallas kernels are ``custom-call``s: their bytes are counted, their FLOPs are
not (the compiler does not know them).  Run by no benchmark cell; of
``benchmark/`` it imports the configuration's reference for its preset, and
restates the grouping rule and the peaks.
"""

import argparse
import collections
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# benchmark/lib/peaks.py, "v5e": Google Cloud TPU documentation, 'TPU v5e'
PEAK_FLOPS, PEAK_BYTES_PER_S = 197e12, 819e9

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
# no work of their own (a while's is its body's; a -start's is its -done's)
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "while", "conditional", "call", "after-all", "partition-id",
         "replica-id", "iota"}


def op_group(name: str) -> str:
    """benchmark/lib/trace_reduce.py::op_group's rule."""
    name = re.sub(r"(\.\d+)+$", "", name.strip().lstrip("%"))
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", name)[:64] or "unnamed"


def _shapes(text: str) -> list:
    """``[(dtype, dims)]`` of every array type spelled in ``text``."""
    return [(t, [int(x) for x in dims.split(",") if x])
            for t, dims in _SHAPE.findall(text)]


def _nbytes(shapes) -> int:
    total = 0
    for dtype, dims in shapes:
        n = _ITEMSIZE.get(dtype, 4)
        for x in dims:
            n *= x
        total += n
    return total


def parse_computations(hlo: str) -> dict:
    """``{computation name: [instruction line]}`` of an HLO module's text."""
    comps, current = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$", line)
        if head:
            current = comps.setdefault(head.group(2), [])
            if head.group(1):
                comps["__entry__"] = current
        elif line.startswith("}"):
            current = None
        elif current is not None and " = " in line:
            current.append(line.strip())
    return comps


def _balanced(text: str, start: int) -> int:
    """Index just past the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i + 1
    return len(text)


def _instruction(line: str):
    """``(name, opcode, result type, operand text, attributes)``: the type
    may be a tuple with layouts and comments inside, so parentheses are
    matched, not guessed."""
    name, _, rhs = line.removeprefix("ROOT ").partition(" = ")
    end = _balanced(rhs, 0) if rhs.startswith("(") else rhs.index(" ")
    result, rest = rhs[:end], rhs[end:].lstrip()
    opcode, _, _ = rest.partition("(")
    close = _balanced(rest, len(opcode))
    return (name.strip().lstrip("%"), opcode, result,
            rest[len(opcode) + 1:close - 1], rest[close:])


def _operand_shapes(operands: str, types: dict) -> list:
    """Operands are printed as names: their types come from where they were
    defined."""
    found = []
    for ref in re.findall(r"%([\w.\-]+)", operands):
        found += types.get(ref, [])
    return found


def conv_flops(line: str, types: dict) -> float:
    """2 · |output| · input features · the window taps that land inside the
    image, per output position.  (A matmul is ``window={size=1}``: one tap.
    The compiler also writes some matmuls with the weight as a 1-wide image
    padded to the batch and the activation as the window: size 12, one tap
    in twelve inside.)  Strides are read, dilations are not."""
    _, _, result, operands, attrs = _instruction(line)
    out = _shapes(result)
    refs = re.findall(r"%([\w.\-]+)", operands)
    labels = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", attrs)
    if (len(refs) < 2 or not out or not labels
            or not types.get(refs[0]) or not types.get(refs[1])):
        return 0.0
    lhs, kernel, out = types[refs[0]][0][1], types[refs[1]][0][1], out[0][1]
    l_lab, k_lab, o_lab = labels.groups()
    window = dict(re.findall(r"(size|pad|stride)=([\dx_\-]+)", attrs))
    spatial = sorted(c for c in k_lab if c.isdigit())
    per_dim = lambda key, default: (window[key].split("x") if key in window
                                    else [default] * len(spatial))
    flops = 2.0 * kernel[k_lab.index("i")]
    for x in out:
        flops *= x
    for c, size, pad, stride in zip(spatial, per_dim("size", "1"),
                                    per_dim("pad", "0_0"),
                                    per_dim("stride", "1")):
        n_in, n_out = lhs[l_lab.index(c)], out[o_lab.index(c)]
        lo = int(pad.split("_")[0])
        inside = sum(0 <= p * int(stride) + k - lo < n_in
                     for p in range(n_out) for k in range(int(size)))
        flops *= inside / n_out
    return flops


def result_types(comps: dict) -> dict:
    """``{instruction name: [(dtype, dims)]}`` over the whole module (names
    are unique in it; parameters are instructions too)."""
    types = {}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        for line in lines:
            iname, _, result, _, _ = _instruction(line)
            types[iname] = _shapes(result)
    return types


def trip_count(comps: dict, while_attrs: str) -> int:
    """How often a ``while`` runs: the compiler's ``known_trip_count`` where
    it printed one, else the one integer constant its condition compares the
    counter with (a ``lax.scan``'s length), else 1."""
    known = re.search(r'known_trip_count[^0-9]*(\d+)', while_attrs)
    if known:
        return int(known.group(1))
    cond = re.search(r"condition=%?([\w.\-]+)", while_attrs)
    bounds = [int(m.group(1)) for line in comps.get(cond.group(1), ())
              for m in [re.search(r"\bs32\[\]\S* constant\((\d+)\)", line)]
              if m] if cond else []
    return bounds[0] if len(bounds) == 1 else 1


def walk(comps: dict, types: dict, name: str, times: int, rows: list,
         touching=()):
    """Append ``(group, runs, flops, bytes)`` for each instruction of
    computation ``name`` that does work, descending into ``while`` bodies
    with their known trip counts multiplied in.  ``touching``: lists of
    dimensions; given any, only instructions with an operand or a result of
    one of those shapes are counted."""
    for line in comps.get(name, ()):
        iname, opcode, result, operands, attrs = _instruction(line)
        if opcode == "while":
            body = re.search(r"body=%?([\w.\-]+)", attrs).group(1)
            walk(comps, types, body, times * trip_count(comps, attrs), rows,
                 touching)
        if opcode in _FREE or opcode.endswith("-start"):
            continue
        if touching and not any(
                dims in touching for _, dims in
                _shapes(result) + _operand_shapes(operands, types)):
            continue
        flops = 0.0
        if opcode == "convolution":
            flops = conv_flops(line, types)
        called = re.search(r"calls=%?([\w.\-]+)", attrs)
        if opcode == "fusion" and called:
            flops = sum(conv_flops(inner, types) for inner in
                        comps.get(called.group(1), ())
                        if " convolution(" in inner)
        if opcode.endswith("-done"):
            # an asynchronous copy or slice: its -start holds operand, result
            # and context in one tuple; what moves is the result, read and
            # written
            nbytes = 2 * _nbytes(_shapes(result))
        else:
            nbytes = _nbytes(_shapes(result)) + _nbytes(
                _operand_shapes(operands, types))
        rows.append((op_group(iname), times, flops, nbytes))


def preset_layers(cfg: dict) -> list:
    """The layer DSL of a benchmark configuration: its reference names the
    preset that builds it (``PRESET``) and with what (``preset_args``)."""
    import importlib
    from penroz_tpu.models import presets
    reference = importlib.import_module(
        "benchmark.reference." + cfg["reference"])
    return getattr(presets, reference.PRESET)(**reference.preset_args(cfg))


def compile_epoch(cfg: dict) -> str:
    """The compiled fast epoch program's HLO text, one described v5e chip."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from penroz_tpu.models import dsl
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import CompiledArch

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    train = cfg["train"]
    steps = train["gradient_accumulation_steps"]
    layers = preset_layers(cfg)
    mapper = Mapper(layers, cfg["optimizer"])
    arch = CompiledArch.get(mapper.layers)
    params, buffers = jax.eval_shape(
        lambda: mapper.init_params(arch.mods, seed=0))
    opt_state = jax.eval_shape(dsl.build_optimizer(cfg["optimizer"]).init,
                               params)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)
    batch = jax.ShapeDtypeStruct(
        (steps, train["batch_size"], train["block_size"]), jnp.int32,
        sharding=chip)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    fn = arch.train_epoch_fn(cfg["optimizer"], steps,
                             compute_dtype=jnp.bfloat16, platform="tpu",
                             with_ratios=False)
    return fn.lower(on_chip(params), on_chip(opt_state), on_chip(buffers),
                    batch, batch, on_chip(rng)).compile().as_text()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", nargs="?",
                    default="benchmark/configs/gpt2-124m-nanogpt.json")
    ap.add_argument("--hlo", help="also write the compiled HLO text here")
    ap.add_argument("--compiled", help="count this compiled HLO text (one "
                    "kept by --hlo) instead of compiling")
    ap.add_argument("--touching", action="append", default=[],
                    metavar="DIMS", help="count only instructions with an "
                    "operand or result of this shape, e.g. 4096,14336 "
                    "(repeatable)")
    args = ap.parse_args()
    with open(args.config) as fh:
        cfg = json.load(fh)
    if args.compiled:
        with open(args.compiled) as fh:
            hlo = fh.read()
    else:
        hlo = compile_epoch(cfg)
    if args.hlo:
        with open(args.hlo, "w") as fh:
            fh.write(hlo)
    touching = [[int(x) for x in dims.split(",")] for dims in args.touching]
    rows = []
    comps = parse_computations(hlo)
    walk(comps, result_types(comps), "__entry__", 1, rows, touching)
    groups = collections.defaultdict(lambda: [0, 0, 0.0, 0.0, 0.0])
    for group, runs, flops, nbytes in rows:
        g = groups[group]
        g[0] += 1
        g[1] += runs
        g[2] += runs * flops
        g[3] += runs * nbytes
        g[4] += runs * max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES_PER_S)
    steps = cfg["train"]["gradient_accumulation_steps"]
    if touching:
        print("instructions touching " + " ".join(args.touching) + " only")
    print(f"{cfg['name']}: one optimizer step = {steps} micro-steps; "
          f"least time at {PEAK_FLOPS / 1e12:.0f} TFLOP/s, "
          f"{PEAK_BYTES_PER_S / 1e9:.0f} GB/s")
    print(f"{'group':34s} {'instr':>6s} {'runs':>7s} {'GFLOP':>10s} "
          f"{'GB':>9s} {'least ms':>9s} {'ms/micro':>9s}")
    ranked = sorted(groups.items(), key=lambda kv: -kv[1][4])
    ranked.append(("total", [sum(g[i] for g in groups.values())
                             for i in range(5)]))
    for group, (n, runs, flops, nbytes, least) in ranked:
        print(f"{group:34s} {n:6d} {runs:7d} {flops / 1e9:10.1f} "
              f"{nbytes / 1e9:9.2f} {least * 1e3:9.2f} "
              f"{least * 1e3 / steps:9.3f}")


if __name__ == "__main__":
    main()
