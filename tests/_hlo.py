"""Reading an optimized HLO module's text (``compiled.as_text()``)."""

import re


def pool_ops(text: str, pool_shape: str) -> list[tuple[str, str]]:
    """(name, opcode) of every instruction of an optimized HLO module whose
    result has ``pool_shape``; a fusion reports its fused root's opcode."""
    inst = re.compile(r"^\s*(ROOT )?%(\S+) = (\S+?)(?:\{[^}]*\})? ([\w\-]+)\((.*)$")
    roots, ops, comp = {}, [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line.rstrip())
        if head:
            comp = head.group(1)
        m = inst.match(line)
        if not m:
            continue
        root, name, shape, op, rest = m.groups()
        if root:
            roots[comp] = (op, rest)
        if shape == pool_shape:
            ops.append((name, op, rest))
    out = []
    for name, op, rest in ops:
        while op == "fusion":
            op, rest = roots[re.search(r"calls=%([\w.\-]+)", rest).group(1)]
        out.append((name, op))
    return out
