"""Name each device op of a traced window after the layer and role that the
program's `jax.named_scope`s give it.

The forward runs every layer under a scope named after its place in the net
(``stem``, ``stages.2.3.c1``, ``pairs.5.dw``, ``head``; `models/cnn.py`), and
the fused conv's steps around its kernel under sub-scopes (``pad``,
``halo``, ``weights``, ``unscramble``; `kernels/log_conv2d.py`).  The
compiler keeps the scopes as each instruction's ``op_name`` metadata
(``jit(resnet34_apply)/stem/jit(log_conv2d_fused_pallas)/pad/...``), and
`compiled.as_text()` prints it.  A device op's trace event is named by the
same instruction, so the map below gives every op its layer with no
profiler option and no cost at run time.

An instruction's role is ``kernel`` for a conv kernel, a Mosaic
``tpu_custom_call`` (not `trace_reduce.is_conv`: at batch 32 XLA lowers the
head's dense layer as a convolution fusion, and the head is no conv layer),
else its innermost scope below the layer, else ``glue`` (bias, ReLU and
whatever else the layer runs).  An instruction with no scope of its own,
such as the layout copy of an argument, takes the layer of the
instructions that read it where they agree on one, and their role where
they agree on one other than ``kernel``; else ``glue``.
"""

from __future__ import annotations

import collections
import re

KERNEL, GLUE = "kernel", "glue"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_OPERAND = re.compile(r"%([^\s,()={}]+)")


def entry_instructions(text: str) -> list[tuple[str, str, str]]:
    """``(name, hlo, op_name)`` of each instruction of the entry
    computation, in program order; ``hlo`` without its metadata, as a
    trace event names the op."""
    out, inside = [], False
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside:
            m = _INSTR.match(line)
            if m:
                meta = _OP_NAME.search(line)
                hlo = _METADATA.sub("", f"%{m.group(1)} = {m.group(2)}")
                out.append((m.group(1), hlo, meta.group(1) if meta else ""))
    return out


def scope_path(op_name: str) -> list[str]:
    """The named scopes of an ``op_name``: its components less the
    transformations and nested jits (``jit(f)``) and less the last, which
    is the primitive.  Where the compiler merged instructions it joins
    their names with ``;``, each after the first without the shared
    prefix; the first, whole one counts."""
    return [c for c in op_name.split(";", 1)[0].split("/")[:-1]
            if "(" not in c]


def layer_role(hlo: str, op_name: str) -> tuple[str | None, str]:
    path = scope_path(op_name)
    if not path:
        return None, GLUE
    if KERNEL_TARGET in hlo:
        return path[0], KERNEL
    return path[0], path[-1] if len(path) > 1 else GLUE


def scope_map(text: str) -> dict[str, tuple[str | None, str]]:
    """``{instruction: (layer, role)}`` for the entry computation of
    ``compiled.as_text()``; parameters are left out, and the layer is None
    where neither the op nor its readers have one."""
    instrs = [i for i in entry_instructions(text)
              if " parameter(" not in i[1]]
    names = {name for name, _, _ in instrs}
    readers = collections.defaultdict(list)
    for name, hlo, _ in instrs:
        rhs = hlo.split(" = ", 1)[1].split(", calls=", 1)[0]
        for operand in set(_OPERAND.findall(rhs)) & names:
            readers[operand].append(name)
    out = {}
    for name, hlo, op_name in reversed(instrs):   # readers come later
        layer, role = layer_role(hlo, op_name)
        if layer is None:
            found = {out[r] for r in readers[name] if out[r][0] is not None}
            if len({lay for lay, _ in found}) == 1:
                roles = {r for _, r in found} - {KERNEL}
                layer = found.pop()[0]
                role = roles.pop() if len(roles) == 1 else GLUE
        out[name] = (layer, role)
    return out


def reduce(text: str, reduced: dict) -> dict:
    """Device time of a traced window by layer and role.

    ``reduced`` is `trace_reduce.reduce`'s for the window with every op
    (``top=None``); its ``busy_s`` is the denominator of the shares.
    ``conv_layers`` are the layers that hold a conv kernel; ``conv_glue_s``
    is the time of their ops that are not the kernel."""
    smap = scope_map(text)
    conv_layers = {lay for lay, role in smap.values() if role == KERNEL}
    by = collections.Counter()
    unmapped = collections.Counter()
    for op, seconds in reduced["top_ops"]:
        layer, role = smap.get(op, (None, GLUE))
        by[(layer, role)] += seconds
        if layer is None:
            unmapped[op] += seconds
    named_s = sum(s for (lay, _), s in by.items() if lay is not None)
    glue_s = sum(s for (lay, role), s in by.items()
                 if lay in conv_layers and role != KERNEL)
    return {"busy_s": reduced["busy_s"], "named_s": named_s,
            "conv_glue_s": glue_s, "conv_layers": sorted(conv_layers),
            "by_layer_role": [[lay, role, s] for (lay, role), s
                              in by.most_common()],
            "unmapped": [[op, s] for op, s in unmapped.most_common(10)]}


def conv_glue(ctx: dict) -> float | None:
    """Device time of the conv layers' ops other than their kernels over
    the device's busy time, in % (``ctx["scopes"]``: `reduce`)."""
    s = ctx.get("scopes")
    if not s or not s["conv_layers"] or s["busy_s"] <= 0:
        return None
    return 100.0 * s["conv_glue_s"] / s["busy_s"]


def table(s: dict, top: int = 15) -> str:
    """The device time by layer and role, largest first, as text lines."""
    busy = s["busy_s"] or 1.0
    rows = [f"{'layer':<24} {'role':<11} {'device s':>10} {'busy %':>7}"]
    for layer, role, sec in s["by_layer_role"][:top]:
        rows.append(f"{layer or '(none)':<24} {role:<11} {sec:>10.6f} "
                    f"{100 * sec / busy:>7.2f}")
    roles = collections.Counter()
    for _, role, sec in s["by_layer_role"]:
        roles[role] += sec
    rows.append("by role: " + ", ".join(f"{role} {100 * sec / busy:.2f}%"
                                         for role, sec in roles.most_common()))
    rows.append(f"in a named layer {100 * s['named_s'] / busy:.2f}% of busy "
                f"time; conv glue {100 * s['conv_glue_s'] / busy:.2f}%")
    return "\n".join(rows)
