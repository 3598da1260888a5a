"""Names for the device ops of a trace, from the compiled programs.

On the TPU a trace names each op by its HLO instruction (``%insert.8``,
``%fusion.2``), and every Pallas kernel's instruction takes the name of
the jitted function around it, so the trace alone cannot tell the probe
kernel from a binning kernel.  The compiled program's HLO text can: a
Pallas kernel is a ``tpu_custom_call`` whose backend config carries the
Mosaic module, and the module names the kernel's function
(``_insert_kernel``); a fusion's metadata names the JAX op it was fused
around (``scatter``, ``gather``).

``labels(hlo_text)`` maps (module, instruction) to a label:

  Pallas kernel        the kernel function's name, e.g. ``_insert_kernel``
  fusion               ``<op>-fusion``, after the JAX op it was fused
                       around (``scatter-fusion``, ``gather-fusion``)
  other custom call    its target, e.g. ``ConcatBitcast``
  anything else        its opcode, e.g. ``sort``, ``all-to-all``, ``while``

A fusion or plain op traced inside a jitted library function gets its
name after an ``@``: ``gather-fusion@searchsorted``.
"""

from __future__ import annotations

import base64
import json
import re

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_KERNEL = re.compile(rb"\b(\w*_kernel)\b")


def _opcode(rhs: str) -> str:
    """The opcode after an instruction's result shape."""
    i = 0
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rhs.find(" ")
    rest = rhs[i:].lstrip()
    return rest[:rest.find("(")] if "(" in rest else rest.split(" ")[0]


def _kernel_name(rhs: str) -> str | None:
    """The kernel function a tpu_custom_call's Mosaic body names first."""
    at = rhs.find("backend_config=")
    if at < 0:
        return None
    cfg = rhs[at + len("backend_config="):]
    cfg = cfg[:cfg.rfind("}") + 1]
    try:
        body = json.loads(cfg)["custom_call_config"]["body"]
    except (ValueError, KeyError, TypeError):
        return None
    # the body's string table starts with the locations of the kernel's
    # own ops; a kernel lowered earlier in the process may leave its
    # name further on
    m = _KERNEL.search(base64.b64decode(body))
    return m.group(1).decode() if m else None


def _within(rhs: str) -> str:
    """``@<f>`` for the innermost public jitted library function the op
    was traced in (``jnp.searchsorted`` -> ``@searchsorted``), else ''."""
    src = re.search(r'op_name="([^"]*)"', rhs)
    names = re.findall(r"jit\(([^)]*)\)", src.group(1)) if src else []
    inner = [n for n in names[1:] if n != names[0] and not n.startswith("_")]
    return f"@{inner[-1]}" if inner else ""


def labels(hlo_text: str) -> dict:
    """{(module, instruction): label} for one compiled program."""
    module = None
    comps: dict[str, list] = {}       # computation -> [(name, opcode, rhs)]
    current = None
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            rhs = m.group(2)
            comps[current].append((m.group(1), _opcode(rhs), rhs))
            continue
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(1)
            comps[current] = []
    ops_in = {c: {op for _, op, _ in ins} for c, ins in comps.items()}
    out = {}
    for ins in comps.values():
        for name, op, rhs in ins:
            if op == "custom-call":
                tgt = re.search(r'custom_call_target="([^"]+)"', rhs)
                target = tgt.group(1) if tgt else "custom-call"
                label = (_kernel_name(rhs) if target == "tpu_custom_call"
                         else None) or target
            elif op == "fusion":
                # named by the JAX op it was fused around, else by what
                # the computation it calls holds
                src = re.search(r'op_name="(?:[^"/]*/)*([^"/]+)"', rhs)
                called = re.search(r"calls=%([\w.\-]+)", rhs)
                inner = ops_in.get(called.group(1), set()) if called else set()
                label = (f"{src.group(1)}-fusion" if src else
                         "scatter-fusion" if "scatter" in inner else
                         "sort-fusion" if "sort" in inner else "fusion")
                label += _within(rhs)
            else:
                label = op + _within(rhs)
            out[(module, name)] = label
    return out


def module_of(event_name: str) -> str:
    """``jit_insert(3641075523008381251)`` -> ``jit_insert``."""
    return event_name.split("(")[0]


def instruction_of(event_name: str) -> str:
    """``%insert.8 = (...) custom-call(...)`` -> ``insert.8``."""
    return event_name.split(" = ")[0].lstrip("%").strip()


def fallback(event_name: str) -> str:
    """A label without the program: the instruction name less its
    number (``fusion.2`` -> ``fusion``)."""
    return re.sub(r"\.\d+$", "", instruction_of(event_name))
