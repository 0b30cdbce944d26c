"""What the benchmark reads from a compiled program's text
(`compiled.as_text()`): its Mosaic kernels and its collectives.

The program's Pallas calls carry no `name=`, so a kernel is known by
what it writes: the job says how many elements an output of its flash
kernel or of its flat optimizer kernel has, and `kernels_writing`
finds the instructions whose results have that many.
"""

from __future__ import annotations

import re

_CUSTOM_CALL = re.compile(
    r"^\s*%?(?P<name>[^\s=]+) = (?P<type>.*?) custom-call\(")
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "collective-permute", "all-to-all")


def custom_calls(text: str) -> list:
    """[(instruction name, [element count of each result array])] of
    every `tpu_custom_call` in the program."""
    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _CUSTOM_CALL.match(line)
        if m is None:
            continue
        sizes = []
        for dims in _ARRAY.findall(m.group("type")):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            sizes.append(n)
        out.append((m.group("name"), sizes))
    return out


def kernels_writing(calls, n_elements: int) -> list:
    """Names of the custom calls one of whose results has `n_elements`."""
    return [name for name, sizes in calls if n_elements in sizes]


def collectives(text: str) -> dict:
    """Collective instructions in the program, counted by kind."""
    return {k: text.count(f" {k}(") + text.count(f" {k}-start(")
            for k in COLLECTIVE_KINDS}
