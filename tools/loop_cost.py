#!/usr/bin/env python3
"""What a compiled closed loop costs a round, branch by branch.

A compiled text in (``jit(...).lower(...).compile().as_text()`` of
``eng._closed_loop`` for a described TPU: the `verify` skill's
rehearsal, no chip), and out of the ``while`` body that holds the
round: every ``conditional`` with where the program wrote it and the
compiler's ``estimated_cycles`` of each branch (a conditional inside a
branch counts by its dearest branch), the flat remainder (what every
round runs whatever it takes), and how often a ``[N, 32]`` ring value
stands N-minor (``,32]{0,1``) and ring-minor (``,32]{1,0``) in the whole
text.

Read the branches, not their sum. A round takes ONE branch of each
conditional, so a change that splits a lane's cond in two (a plain
branch and a whole one, PR 43) adds a branch to the all-branches sum
and looks dearer by the very branch it saves; summed over all branches
such a change is mis-ranked against one that adds none. ``--take``
sums a scenario instead: the flat part and the named branch of each
conditional in the order listed (``1,0,-,...``: ``-`` or nothing is
the dearest branch).

    python3 tools/loop_cost.py loop.txt
    python3 tools/loop_cost.py loop.txt --take 0,1,0,1,0,1,1,1 --json

The cycles are the compiler's estimate, not a time: it over-prices
reduce fusions by up to two (PERF.md section 6, "PR 39"), a ``sort`` or
a collective carries none, and on the chip a cycle of the estimate has
read 0.23 ns. Run by no cell and no test but its own.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Dict, List, NamedTuple, Optional

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^ ]+) \(.*\{\s*$")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_BRANCHES = re.compile(
    r"branch_computations=\{([^}]*)\}"
    r"|true_computation=%([^ ,)]+), false_computation=%([^ ,)]+)")
_CALLED = re.compile(r"(?:to_apply|calls)=%([^ ,)]+)")
_BODY = re.compile(r"\bbody=%([^ ,)]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_OPCODE = re.compile(r"^\s*(?:ROOT )?%[^ ]+ = .*?\s([a-z][a-z0-9-]*)\(")
RING_N_MINOR, RING_RING_MINOR = ",32]{0,1", ",32]{1,0"


class Cond(NamedTuple):
    name: str          # the instruction's
    op_name: str       # the metadata's: the named scopes it stands under
    where: str         # the source lines that wrote it, innermost first
    branches: List[int]  # estimated cycles of each


class Loop(NamedTuple):
    body: str
    flat: int
    conds: List[Cond]
    ring_n_minor: int
    ring_ring_minor: int


class _Text:
    def __init__(self, text: str):
        self.computations: Dict[str, List[str]] = {}
        self._frames = self._tables(text)
        name = None
        for line in text.splitlines():
            m = _COMPUTATION.match(line)
            if m:
                name = m.group(1)
                self.computations[name] = []
            elif line.startswith("}"):
                name = None
            elif name is not None:
                self.computations[name].append(line)

    @staticmethod
    def _tables(text: str):
        """stack frame id -> (function, line, parent id) from the
        module's FileLocations / StackFrames tables, where it has
        them."""
        tables: Dict[str, Dict[int, str]] = {}
        current = None
        for line in text.splitlines():
            if line.startswith(("%", "ENTRY")):
                break  # the tables stand before the first computation
            if line in ("FileNames", "FunctionNames", "FileLocations",
                        "StackFrames"):
                current = tables.setdefault(line, {})
            elif current is not None:
                m = re.match(r"^(\d+) (.*)$", line)
                if m:
                    current[int(m.group(1))] = m.group(2)
                else:
                    current = None
        field = lambda row, key: int(  # noqa: E731
            re.search(rf"{key}=(\d+)", row).group(1))
        frames = {}
        for fid, row in tables.get("StackFrames", {}).items():
            loc = tables["FileLocations"].get(field(row, "file_location_id"))
            if loc is None:
                continue
            fn = tables["FunctionNames"].get(
                field(loc, "function_name_id"), '"?"').strip('"')
            # (The text prints a parent as its id plus one; 1 is none.)
            frames[fid] = (fn.split(".")[-1], field(loc, "line"),
                           field(row, "parent_frame_id") - 1)
        return frames

    def where(self, line: str, depth: int = 3) -> str:
        m = _FRAME.search(line)
        fid = int(m.group(1)) if m else None
        out = []
        while fid in self._frames and len(out) < depth:
            fn, at, parent = self._frames[fid]
            out.append(f"{fn}:{at}")
            fid = parent
        return " < ".join(out)

    def cost(self, name: str) -> int:
        """Estimated cycles of one run of a computation: its own
        instructions, what it calls, its conditionals by their dearest
        branch and a nested loop's body once."""
        total = 0
        for line in self.computations.get(name, ()):
            total += self._own(line)
            branches = self._branches(line)
            if branches:
                total += max(self.cost(b) for b in branches)
        return total

    def _own(self, line: str) -> int:
        """An instruction's own cycles, with those of a computation it
        calls that carries cycles of its own inside (a ``call``; a
        fusion's are on the fusion)."""
        m = _CYCLES.search(line)
        if m:
            return int(m.group(1))
        op = _OPCODE.match(line)
        if op and op.group(1) in ("call", "while"):
            callee = (_BODY if op.group(1) == "while" else _CALLED).search(
                line)
            return self.cost(callee.group(1)) if callee else 0
        return 0

    @staticmethod
    def _branches(line: str) -> Optional[List[str]]:
        if " conditional(" not in line:
            return None
        m = _BRANCHES.search(line)
        if m is None:
            return None
        if m.group(1) is not None:
            return [b.strip().lstrip("%") for b in m.group(1).split(",")]
        # A predicated conditional lists the taken branch first; as
        # indexes the not-taken one is 0.
        return [m.group(3), m.group(2)]

    def conditionals(self, name: str) -> int:
        return sum(" conditional(" in line
                   for line in self.computations.get(name, ()))


def read(text: str) -> Loop:
    """The round's loop of a compiled text: the ``while`` body with the
    most conditionals written straight into it."""
    t = _Text(text)
    bodies = {m.group(1) for lines in t.computations.values()
              for line in lines for m in [_BODY.search(line)]
              if m and " while(" in line}
    if not bodies:
        raise ValueError("no while loop in this text")
    body = max(sorted(bodies), key=t.conditionals)
    flat, conds = 0, []
    for line in t.computations[body]:
        branches = t._branches(line)
        if branches is None:
            flat += t._own(line)
            continue
        name = re.match(r"^\s*(?:ROOT )?%([^ ]+) =", line).group(1)
        op = _OP_NAME.search(line)
        conds.append(Cond(name, op.group(1) if op else "", t.where(line),
                          [t.cost(b) for b in branches]))
    return Loop(body, flat, conds, text.count(RING_N_MINOR),
                text.count(RING_RING_MINOR))


def scenario(loop: Loop, take: str) -> int:
    """The flat part and one branch of each conditional: `take` is a
    comma-separated branch index a conditional, in the order listed;
    ``-``, nothing, or a conditional past its end is the dearest."""
    picks = [p.strip() for p in take.split(",")] if take else []
    if len(picks) > len(loop.conds):
        raise ValueError(
            f"{len(picks)} branches named for {len(loop.conds)} conditionals")
    total = loop.flat
    for i, cond in enumerate(loop.conds):
        pick = picks[i] if i < len(picks) else "-"
        total += (max(cond.branches) if pick in ("", "-")
                  else cond.branches[int(pick)])
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("text", help="a compiled closed loop's as_text(), a file")
    ap.add_argument("--take", default=None,
                    help="branch of each conditional to sum, in order: "
                    "0,1,-,... (- is the dearest)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    with open(args.text) as f:
        loop = read(f.read())
    out = loop._asdict()
    out["conds"] = [c._asdict() for c in loop.conds]
    out["dearest"] = scenario(loop, "")
    if args.take is not None:
        out["taken"] = scenario(loop, args.take)
    if args.json:
        print(json.dumps(out))
        return 0
    print(f"loop body {loop.body}: flat {loop.flat:,} cycles, "
          f"{len(loop.conds)} conditionals; ring values N-minor "
          f"{loop.ring_n_minor}, ring-minor {loop.ring_ring_minor}")
    for i, c in enumerate(loop.conds):
        scope = (re.findall(r"raft_[a-z_]+", c.op_name) or [""])[-1]
        print(f"  [{i:2d}] {c.name:<14} {scope:<13} "
              f"{' | '.join(f'{b:,}' for b in c.branches):<40} {c.where}")
    print(f"flat + the dearest branch of each: {out['dearest']:,}")
    if "taken" in out:
        print(f"flat + the branches taken ({args.take}): {out['taken']:,}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
