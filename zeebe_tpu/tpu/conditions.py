"""json-el conditions → device predicate programs.

The reference evaluates exclusive-gateway conditions per record with a tree
interpreter over msgpack (``json-el/.../JsonConditionInterpreter.java``);
here each condition compiles once (at deployment) to a postfix program over
columnarized payload variables, and the kernel evaluates ALL records × ALL
outgoing flows in parallel with a fixed-depth stack machine (lax.scan over
instructions).

Tri-state logic preserves the oracle's short-circuit error semantics
(``zeebe_tpu/models/el/interpreter.py``): FALSE=0, TRUE=1, ERROR=2;
``and``: F→F, E→E, else right; ``or``: T→T, E→E, else right. A comparison
errors when a referenced variable is absent, types mismatch (int/float
widen), or ordering is applied to non-numbers — exactly the oracle's raise
conditions, so an ERROR result maps to the same CONDITION_ERROR incident.

Strings compare by interned id (exact); numbers compare as float32 —
sound because every number a program reads is f32-EXACT: literal
compilation sends a workflow with an inexact literal to the host engine,
and a payload number f32 does not hold is held on the host (``VT_HELD``,
``tpu/values.py``) exactly like a map or a list, where the engine sends an
instance whose workflow reads that variable to the host engine, which
compares in float64. So no held value ever reaches ``_compare``; and
f64→f32 is order-preserving on exactly-representable values.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from zeebe_tpu.models.el.ast import (
    Comparison,
    Condition,
    Conjunction,
    Disjunction,
    JsonPathLiteral,
    Literal,
    compile_json_path,
)
from zeebe_tpu.tpu.intern import InternTable

# tri-state
FALSE, TRUE, ERROR = 0, 1, 2

# opcodes
OP_NOP, OP_CMP, OP_AND, OP_OR = 0, 1, 2, 3

# comparison ops
CMP_OPS = {"==": 0, "!=": 1, "<": 2, "<=": 3, ">": 4, ">=": 5}

# operand kinds
K_VAR, K_NUM, K_STR, K_BOOL, K_NIL = 0, 1, 2, 3, 4

# variable value types (the ``vtype`` payload column); INTEGER and FLOAT are
# distinct for document round-trips but compare as one numeric class
# (the oracle's int/float widening, interpreter.py _coerce_same_type).
# VT_HELD: a value with no scalar column form (a map, a list, a number f32
# does not hold exactly), its ``v_str`` cell a handle into the engine's
# host-side value store (``tpu/values.py``); never an operand here
VT_ABSENT, VT_NIL, VT_BOOL, VT_NUM, VT_STR, VT_FLOAT, VT_HELD = 0, 1, 2, 3, 4, 5, 6

STACK_DEPTH = 8


def f32_exact(value: float) -> bool:
    """True when ``value`` survives a float32 round trip exactly. The
    device engine stores payload numerics as f32 (see state.pack_payload);
    BOTH gates — payload columnarization (batch.payload_to_columns) and
    condition-literal compilation below — must use this same predicate, or
    the "only f32-exact values reach the device" soundness argument of this
    module's header breaks. An int compares as an int: in float64 an int
    above 2^53 rounds before it is compared."""
    try:
        f = np.float32(value)
    except OverflowError:
        return False
    if not np.isfinite(f):
        return False
    if isinstance(value, int):
        return int(f) == value
    return float(f) == float(value)


class DeviceIneligible(ValueError):
    """Condition uses a feature the device path cannot evaluate (nested
    JSONPath, non-scalar literal) — the workflow falls back to the host
    oracle engine."""


@dataclasses.dataclass
class ProgramPool:
    """Host-side accumulator for compiled programs; ``tensors`` yields the
    device form."""

    varspace: "object"  # VarSpace (graph.py); needs .column(name)
    interns: InternTable
    programs: List[List[Tuple[int, int, int, int, int, int]]] = dataclasses.field(
        default_factory=list
    )
    lit_nums: List[float] = dataclasses.field(default_factory=list)

    def _num_literal(self, value: float) -> int:
        if not f32_exact(value):
            raise DeviceIneligible(
                f"condition literal not f32-exact: {value!r}"
            )
        self.lit_nums.append(float(value))
        return len(self.lit_nums) - 1

    def _operand(self, operand) -> Tuple[int, int]:
        if isinstance(operand, JsonPathLiteral):
            steps = compile_json_path(operand.path)
            if len(steps) != 1 or not isinstance(steps[0], str):
                raise DeviceIneligible(
                    f"non-flat JSONPath in condition: {operand.path}"
                )
            return K_VAR, self.varspace.column(steps[0])
        assert isinstance(operand, Literal)
        v = operand.value
        if v is None:
            return K_NIL, 0
        if isinstance(v, bool):
            return K_BOOL, 1 if v else 0
        if isinstance(v, (int, float)):
            return K_NUM, self._num_literal(v)
        if isinstance(v, str):
            return K_STR, self.interns.intern(v)
        raise DeviceIneligible(f"non-scalar literal in condition: {v!r}")

    def _emit(self, condition: Condition, out: list) -> None:
        if isinstance(condition, Comparison):
            lk, li = self._operand(condition.left)
            rk, ri = self._operand(condition.right)
            out.append((OP_CMP, CMP_OPS[condition.op], lk, li, rk, ri))
        elif isinstance(condition, Conjunction):
            self._emit(condition.left, out)
            self._emit(condition.right, out)
            out.append((OP_AND, 0, 0, 0, 0, 0))
        elif isinstance(condition, Disjunction):
            self._emit(condition.left, out)
            self._emit(condition.right, out)
            out.append((OP_OR, 0, 0, 0, 0, 0))
        else:
            raise DeviceIneligible(f"unknown condition node: {condition!r}")

    def compile(self, condition: Condition) -> int:
        """Compile one condition; returns its program id."""
        out: list = []
        self._emit(condition, out)
        self.programs.append(out)
        return len(self.programs) - 1

    def tensors(self):
        """(progs [P, L, 6] i32, lit_nums [Q] f32), padded to coarse sizes
        so kernel jit caches are shared across deployments."""

        def _pad(n: int, mult: int) -> int:
            return ((max(n, 1) + mult - 1) // mult) * mult

        max_len = _pad(max((len(p) for p in self.programs), default=0), 8)
        count = _pad(len(self.programs), 4)
        arr = [
            [list(ins) for ins in p] + [[OP_NOP] * 6] * (max_len - len(p))
            for p in self.programs
        ]
        arr += [[[OP_NOP] * 6] * max_len] * (count - len(arr))
        progs = jnp.array(arr, dtype=jnp.int32).reshape(count, max_len, 6)
        lits = list(self.lit_nums)
        lits += [0.0] * (_pad(len(lits), 8) - len(lits))
        lit_nums = jnp.array(lits, dtype=jnp.float32)
        return progs, lit_nums


def _resolve(kind, idx, v_vt, v_num, v_str, lit_nums):
    """Operand → (vtype, num, sid). ``kind``/``idx`` broadcast over the
    query shape; v_* are [..., V] payload columns."""
    var_vt = jnp.take_along_axis(v_vt, idx[..., None], axis=-1)[..., 0]
    var_num = jnp.take_along_axis(v_num, idx[..., None], axis=-1)[..., 0]
    var_str = jnp.take_along_axis(v_str, idx[..., None], axis=-1)[..., 0]
    lit_num = lit_nums[jnp.clip(idx, 0, lit_nums.shape[0] - 1)]

    vt = jnp.select(
        [kind == K_VAR, kind == K_NUM, kind == K_STR, kind == K_BOOL],
        [var_vt, VT_NUM, VT_STR, VT_BOOL],
        VT_NIL,
    )
    num = jnp.select(
        [kind == K_VAR, kind == K_NUM, kind == K_BOOL],
        [var_num, lit_num, idx.astype(jnp.float32)],
        jnp.float32(0.0),
    )
    sid = jnp.select(
        [kind == K_VAR, kind == K_STR],
        [var_str, idx],
        0,
    )
    return vt, num, sid


def _compare(op, lvt, lnum, lsid, rvt, rnum, rsid):
    """Tri-state comparison, oracle semantics."""
    absent = (lvt == VT_ABSENT) | (rvt == VT_ABSENT)
    any_nil = (lvt == VT_NIL) | (rvt == VT_NIL)
    both_nil = (lvt == VT_NIL) & (rvt == VT_NIL)
    l_num_t = (lvt == VT_NUM) | (lvt == VT_FLOAT)
    r_num_t = (rvt == VT_NUM) | (rvt == VT_FLOAT)
    same_type = (lvt == rvt) | (l_num_t & r_num_t)

    eq_raw = jnp.select(
        [lvt == VT_STR, lvt == VT_BOOL],
        [lsid == rsid, lnum == rnum],
        lnum == rnum,  # numeric
    )
    # equality: nil equals only nil (no error); else same type required
    eq_err = (~any_nil) & (~same_type)
    eq_val = jnp.where(any_nil, both_nil, eq_raw)
    eq_tri = jnp.where(eq_err, ERROR, eq_val.astype(jnp.int32))
    ne_tri = jnp.where(eq_err, ERROR, (~eq_val).astype(jnp.int32))

    # ordering: numbers only
    ord_err = ~(l_num_t & r_num_t)
    ord_raw = jnp.select(
        [op == 2, op == 3, op == 4],
        [lnum < rnum, lnum <= rnum, lnum > rnum],
        lnum >= rnum,
    )
    ord_tri = jnp.where(ord_err, ERROR, ord_raw.astype(jnp.int32))

    tri = jnp.select([op == 0, op == 1], [eq_tri, ne_tri], ord_tri)
    return jnp.where(absent, ERROR, tri)


def _combine_and(a, b):
    return jnp.where(a == FALSE, FALSE, jnp.where(a == ERROR, ERROR, b))


def _combine_or(a, b):
    return jnp.where(a == TRUE, TRUE, jnp.where(a == ERROR, ERROR, b))


def eval_programs(progs, lit_nums, prog_id, v_vt, v_num, v_str):
    """Evaluate programs for a batch of queries.

    prog_id: [...] i32 (clipped; callers mask out -1 themselves)
    v_vt/v_num/v_str: [..., V] payload columns (same leading shape)
    returns tri-state [...] i32
    """
    pid = jnp.clip(prog_id, 0, progs.shape[0] - 1)
    code = progs[pid]  # [..., L, 6]
    length = progs.shape[1]
    shape = prog_id.shape

    stack0 = jnp.zeros(shape + (STACK_DEPTH,), dtype=jnp.int32)
    sp0 = jnp.zeros(shape, dtype=jnp.int32)
    lanes = jnp.arange(STACK_DEPTH, dtype=jnp.int32)

    def step(carry, i):
        stack, sp = carry
        ins = code[..., i, :]  # [..., 6]
        opcode = ins[..., 0]
        is_cmp = opcode == OP_CMP
        is_and = opcode == OP_AND
        is_or = opcode == OP_OR

        lvt, lnum, lsid = _resolve(
            ins[..., 2], ins[..., 3], v_vt, v_num, v_str, lit_nums
        )
        rvt, rnum, rsid = _resolve(
            ins[..., 4], ins[..., 5], v_vt, v_num, v_str, lit_nums
        )
        cmp_tri = _compare(ins[..., 1], lvt, lnum, lsid, rvt, rnum, rsid)

        # pop two for AND/OR
        top = jnp.take_along_axis(
            stack, jnp.maximum(sp - 1, 0)[..., None], axis=-1
        )[..., 0]
        under = jnp.take_along_axis(
            stack, jnp.maximum(sp - 2, 0)[..., None], axis=-1
        )[..., 0]
        comb = jnp.where(is_and, _combine_and(under, top), _combine_or(under, top))

        is_bin = is_and | is_or
        push_val = jnp.where(is_cmp, cmp_tri, comb)
        push_pos = jnp.where(is_bin, jnp.maximum(sp - 2, 0), sp)
        write = (is_cmp | is_bin)[..., None] & (lanes == push_pos[..., None])
        stack = jnp.where(write, push_val[..., None], stack)
        sp = jnp.where(is_cmp, sp + 1, jnp.where(is_bin, sp - 1, sp))
        return (stack, sp), None

    (stack, _), _ = lax.scan(step, (stack0, sp0), jnp.arange(length))
    return stack[..., 0]
