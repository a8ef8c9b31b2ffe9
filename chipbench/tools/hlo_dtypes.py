#!/usr/bin/env python3
"""Which widths the fused serve step's matrix products read, on the chip.

    python3 chipbench/tools/hlo_dtypes.py [--config mixtral-8x7b] \
        [--width 16]

Builds the serving engine for a configuration as ``run.py`` does (its
weights from seed 0, the configuration's slots and cache), lowers its
fused serve step at chunk width ``--width`` (C micro-steps in one
call), compiles it for the chip, and reads the compiled HLO text:

- every ``dot`` and ``convolution``: the top-level op that runs it
  (the name the profiler's trace gives), its named scope, its operand
  types, the types at which that op reads them (``reads``: through any
  conversion fused into it; ``S(1)`` marks the on-chip memory space),
  and whether it runs inside a while loop's body (once per micro-step)
  or outside (once per call);
- every ``convert``, or ``copy`` to another type, of a floating array of
  a million elements or more to a narrower floating type, with the same.

Prints one JSON line, also appended to ``chiprun_out/hlo_dtypes.jsonl``;
the HLO text goes to ``chiprun_out/hlo/``.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

OUT = HERE.parent / "chiprun_out"

FLOAT_BITS = {"f64": 64, "f32": 32, "bf16": 16, "f16": 16,
              "f8e4m3fn": 8, "f8e5m2": 8}
BIG = 1 << 20        # elements: a weight, not a per-token activation

_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_TYPE = re.compile(r"^(\w+)\[([\d,]*)\]")
_CALLS = re.compile(r"\b(calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SPACE = re.compile(r"S\((\d+)\)\}$")
# ops that move or convert a value without computing on it
_MOVES = ("bitcast", "copy", "convert", "reshape", "transpose",
          "broadcast")


def _operands(rest: str) -> List[str]:
    """Operand names of an instruction, from the text after its
    opcode's opening parenthesis."""
    depth, out, cur = 1, [], ""
    for ch in rest:
        if ch in "({":
            depth += 1
        elif ch in ")}":
            depth -= 1
            if depth == 0:
                break
        if ch == "," and depth == 1:
            out.append(cur)
            cur = ""
        else:
            cur += ch
    out.append(cur)
    return [o.strip().split()[-1].lstrip("%") for o in out if o.strip()]


def parse(text: str) -> Dict[str, Dict]:
    """Computations of an HLO module's text: ``{name: {"entry": bool,
    "instrs": [{name, type, op, operands, calls, op_name}]}}``, where
    ``calls`` holds ``(kind, computation)`` pairs, ``kind`` being
    ``calls``, ``body``, ``condition``, ``to_apply`` or ``branch``."""
    comps: Dict[str, Dict] = {}
    cur = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps[m.group(2)] = {"entry": bool(m.group(1)),
                                       "instrs": []}
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, typ, op, rest = m.groups()
        calls = _CALLS.findall(rest)
        for group in _BRANCHES.findall(rest):
            calls += [("branch", b.strip().lstrip("%"))
                      for b in group.split(",") if b.strip()]
        on = _OP_NAME.search(rest)
        cur["instrs"].append({"name": name, "type": typ, "op": op,
                              "operands": _operands(rest), "calls": calls,
                              "op_name": on.group(1) if on else ""})
    return comps


def _dtype(typ: str):
    m = _TYPE.match(typ)
    if not m:
        return None, 0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return m.group(1), n


def _space(typ: str) -> str:
    """The memory space a value's layout names: ``S(1)`` is the chip's
    on-chip memory, no mark its HBM."""
    m = _SPACE.search(typ)
    return f" S({m.group(1)})" if m else ""


def analyse(text: str) -> Dict:
    """The products and narrowing conversions of an HLO module's text,
    each with the top-level op that runs it (the op the profiler's trace
    names) and whether it runs inside a while loop's body.  For each
    product, ``reads`` gives the types at which that top-level op takes
    the product's operands: a conversion fused into it is looked
    through to the array it reads.  The compiler also converts by
    ``copy`` to another type, so a narrowing ``copy`` counts."""
    comps = parse(text)
    index = {n: {i["name"]: i for i in c["instrs"]} for n, c in comps.items()}
    caller: Dict[str, tuple] = {}      # computation -> (computation, instr)
    loop_bodies, schedules = set(), {n for n, c in comps.items()
                                     if c["entry"]}
    for cname, comp in comps.items():
        for ins in comp["instrs"]:
            for kind, callee in ins["calls"]:
                caller.setdefault(callee, (cname, ins["name"]))
                if kind == "body":
                    loop_bodies.add(callee)
                if kind in ("body", "condition", "branch") \
                        or ins["op"] == "call":
                    schedules.add(callee)

    def top(cname: str, iname: str):
        """Climb out of fusions and reducers to the computation whose
        instructions are the device's ops; note any loop body on the
        way up to the entry."""
        in_loop = cname in loop_bodies
        while cname not in schedules and cname in caller:
            cname, iname = caller[cname]
            in_loop |= cname in loop_bodies
        while cname in caller:
            cname = caller[cname][0]
            in_loop |= cname in loop_bodies
        return iname, in_loop

    def reads(cname: str, name: str) -> str:
        """The type at which the top-level op takes a value used inside
        it: through moves, conversions and one-input fusions, out of
        each fusion's parameter to its caller's operand, and back
        through asynchronous copies (a prefetch into on-chip memory)
        to the array they copy."""
        while True:
            ins = index[cname].get(name)
            if ins is None:
                return "?"
            if cname in schedules or cname not in caller:
                if ins["op"] in ("bitcast", "copy-done", "copy-start"):
                    name = ins["operands"][0]
                    continue
                return short(ins["type"]) + _space(ins["type"])
            if ins["op"] == "parameter":
                cname, iname = caller[cname]
                name = index[cname][iname]["operands"][int(ins["operands"][0])]
            elif ins["op"] in _MOVES or (ins["op"] == "fusion"
                                         and len(ins["operands"]) == 1):
                name = ins["operands"][0]
            else:
                return short(ins["type"]) + _space(ins["type"])

    def short(typ: str) -> str:
        return typ.split("{")[0]

    products, converts = [], []
    for cname, comp in comps.items():
        types = {i["name"]: i["type"] for i in comp["instrs"]}
        for ins in comp["instrs"]:
            if ins["op"] in ("dot", "convolution"):
                op, in_loop = top(cname, ins["name"])
                products.append({
                    "op": op, "in_loop": in_loop, "scope": ins["op_name"],
                    "operands": [short(types.get(o, "?"))
                                 for o in ins["operands"]],
                    "reads": [reads(cname, o) for o in ins["operands"]],
                    "result": short(ins["type"])})
            elif ins["op"] in ("convert", "copy") and ins["operands"]:
                src_type = types.get(ins["operands"][0], "")
                src, n = _dtype(src_type)
                dst, _ = _dtype(ins["type"])
                if (n >= BIG and src in FLOAT_BITS and dst in FLOAT_BITS
                        and FLOAT_BITS[dst] < FLOAT_BITS[src]):
                    op, in_loop = top(cname, ins["name"])
                    converts.append({
                        "op": op, "in_loop": in_loop, "scope": ins["op_name"],
                        "from": short(src_type), "to": short(ins["type"])})
    return {"products": products, "converts": converts}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mixtral-8x7b")
    ap.add_argument("--width", type=int, default=16)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("hlo_dtypes: no TPU", file=sys.stderr)
        return 1
    import lm_weights
    import run
    from repro.serve.engine import ServingEngine
    from systems.lm_serve import program_config
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(run.ROOT / ".jax_cache"))
    c = run.load_config(args.config)
    sv = c["serving"]
    eng = ServingEngine(program_config(c), lm_weights.make(c, 0),
                        batch_slots=sv["slots"], cache_len=sv["cache_len"],
                        prefill_chunk=sv["prefill_chunk"])
    B, C = sv["slots"], args.width
    batch = {"tokens": jnp.zeros((B, C), jnp.int32),
             **{k: jnp.zeros(B, jnp.int32)
                for k in ("start", "pos", "lengths", "adv")}}
    compiled = eng._step_fn.lower(eng.params, eng.caches, batch).compile()
    text = compiled.as_text()
    (OUT / "hlo").mkdir(parents=True, exist_ok=True)
    (OUT / "hlo" / f"serve_chunk_step_{args.config}_C{C}.txt").write_text(
        text)
    got = analyse(text)
    line = {"config": args.config, "width": C,
            "device": jax.devices()[0].device_kind, **got}
    out = json.dumps(line)
    print(out, flush=True)
    with open(OUT / "hlo_dtypes.jsonl", "a") as f:
        f.write(out + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
