"""The phases of the actor/learner/replay step, and the map from a
compiled chunk's instructions back to them.

``make_step`` (runtime/loop.py) and the learner calls
(``make_learner_step``, runtime/learner.make_sharded_learn) wrap each
phase in ``jax.named_scope(<phase>)``.  A scope changes only the HLO
metadata of the ops the phase lowers to (``op_name="jit(chunk)/while/
body/learn/.../sample/..."``), never the ops.  A profile names each
device op by its HLO instruction, and instruction names are unique
within a module, so a profile's op time joins to a phase through
``op_phases`` of the compiled chunk's text (``Executor.op_phases``):

    phase_of_op = ex.op_phases(state)          # {"fusion.123": "sample", ...}
    for op, ns in op_time.items():             # from any profile
        per_phase[phase_of_op.get(op)] += ns   # None: no phase

  * ``act``            — the rng split, ε and the actors' env step
  * ``insert_begin``   — zero the in-flight slots' leaf priorities
  * ``flush``          — the tree's upward propagation pass (the
                         iteration's, and those between learner calls)
  * ``learn``          — the learn ``lax.cond`` and its bookkeeping
  * ``sample``         — the PER draw and gather (with the sharded
                         buffer's global-stats psum/pmax)
  * ``learner_update`` — forward, backward and the optimizer step
  * ``grad_reduce``    — the cross-shard gradient (or parameter) reduce
  * ``write_back``     — the priority write-back
  * ``insert_commit``  — the storage write and P_max restore
  * ``publish``        — the async loop's acting-copy republish

No phase is named after a JAX primitive, so an ``op_name`` segment that
equals a phase is the scope and not an op.  A jitted helper called with
the same argument shapes from two phases (``jnp.where`` is one) is
lowered once per module, so all its ops carry the phase of the call
lowered first.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

PHASES = ("act", "insert_begin", "flush", "learn", "sample",
          "learner_update", "grad_reduce", "write_back", "insert_commit",
          "publish")

_PHASE_SET = frozenset(PHASES)
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.-]+)\s+=\s")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
# computations an instruction runs (a fusion's, a while's, a reducer's);
# a conditional's branches are the program's own ``lax.cond`` and keep
# their ops' own op_names, so nothing is inherited through them
_CALLEE = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.-]+)")


def phase_of(op_name: str) -> Optional[str]:
    """The innermost phase in an ``op_name`` path (a flush inside
    ``learn`` is ``flush``), or None if no phase is in it."""
    for part in reversed(op_name.split("/")):
        if part in _PHASE_SET:
            return part
    return None


def op_phases(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: phase}`` of a compiled module's text
    (``compiled.as_text()``); instructions with no phase are left out.

    An instruction's phase is that of its own ``op_name``.  One without
    (XLA made it: a gather expanded into a ``while`` over the batch
    keeps the gather's ``op_name`` on the ``while`` only) inside a
    fusion's, a ``while``'s or a reducer's computation takes the phase
    of the instruction that calls that computation, where every caller
    agrees on one."""
    own: Dict[str, Optional[str]] = {}
    home: Dict[str, str] = {}                   # instruction → computation
    callers: Dict[str, List[str]] = {}          # computation → instructions
    comp = None
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            m = _HEADER.match(line)
            comp = m.group(1) if m and not line.startswith("HloModule") \
                else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        home[name] = comp
        op = _OP_NAME.search(line)
        own[name] = phase_of(op.group(1)) if op else None
        for callee in _CALLEE.findall(line):
            callers.setdefault(callee, []).append(name)

    inherited: Dict[str, Optional[str]] = {}

    def computation_phase(c: str) -> Optional[str]:
        if c not in inherited:
            inherited[c] = None                 # a cycle inherits nothing
            found = {phase(i) for i in callers.get(c, ())}
            inherited[c] = found.pop() if len(found) == 1 else None
        return inherited[c]

    def phase(i: str) -> Optional[str]:
        return own[i] or computation_phase(home[i])

    return {i: p for i in own if (p := phase(i)) is not None}
