"""Donation/lease-discipline pass (DN): no use after buffer hand-off.

Three hand-off protocols delete or transfer buffer ownership in this
codebase, and using a buffer after any of them is at best a crash and
at worst silent corruption (the exact failure mode
``DeviceStateManager.lease_packed`` exists to prevent):

- ``donate_argnums``: a jitted callable built with donation DELETES its
  donated input buffers when called.  ``DN001`` flags any later read of
  a variable passed in a donated position.  Donating callables are
  recognized from ``jax.jit(f, donate_argnums=(...))`` bindings in the
  same function, from configured constructors (``build_packed_chain``
  and the single step's ``build_packed_step`` donate argument 1 of the
  callable they return unless built with a literal ``donate=False``),
  and from configured parameter names
  (a parameter named ``chain`` is assumed donating at position 1 — the
  dispatcher's hand-off convention).
- lease/commit: after ``commit_packed(..., lease_token=token)`` closes
  the lease opened by ``ps, token = lease_packed()``, the leased packed
  epoch's buffers may have been donated away — ``DN002`` flags any
  later read of the leased variable.
- reservation close: after ``r.commit()`` / ``r.abort()`` on a value
  obtained from ``.reserve(...)`` or ``Reservation(...)``, the buffers
  belong to the batcher (or to nobody) — ``DN003`` flags later reads.
  The defining class's own methods are exempt (the implementation must
  touch its own buffers).

The analysis is function-local and source-ordered: a donation event at
line N flags loads of the same name at lines > N in the same function
body.  Re-binding the name (a fresh assignment) clears the taint.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from sitewhere_tpu.analysis.core import (
    Finding,
    FuncInfo,
    Project,
    dotted_name,
    iter_scope,
)

PASS_ID = "donation"

# constructors returning donating callables: name -> (donated arg index
# of the RETURNED callable, kwarg that disables donation when False)
DEFAULT_DONATING_BUILDERS: Dict[str, Tuple[int, Optional[str]]] = {
    "build_packed_chain": (1, "donate"),
    "build_sharded_step": (1, "donate"),
    # the single step, donating under DeviceStateManager.step_packed
    "build_packed_step": (1, "donate"),
    "build_sharded_packed_step": (1, "donate"),
    "_ring_chain": (1, None),   # dispatcher accessor over the chain cache
}
# parameters assumed to BE donating callables: param name -> donated idx
DEFAULT_DONATING_PARAMS: Dict[str, int] = {"chain": 1}
# reservation-producing calls (attribute or name suffixes)
_RESERVE_PRODUCERS = {"reserve", "Reservation"}
_CLOSE_METHODS = {"commit", "abort"}
_LEASE_METHODS = {"lease_packed"}


class DonationPass:
    pass_id = PASS_ID

    def __init__(self,
                 donating_builders: Optional[Dict] = None,
                 donating_params: Optional[Dict[str, int]] = None,
                 reservation_exempt_classes: Sequence[str] = ("Reservation",
                                                             "Batcher")):
        self.builders = dict(DEFAULT_DONATING_BUILDERS
                             if donating_builders is None
                             else donating_builders)
        self.donating_params = dict(DEFAULT_DONATING_PARAMS
                                    if donating_params is None
                                    else donating_params)
        self.exempt_classes = frozenset(reservation_exempt_classes)

    def run(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        for qn, fi in sorted(project.functions.items()):
            findings.extend(self._check_function(project, fi))
        return findings

    # -- per-function flow ---------------------------------------------------

    def _check_function(self, project: Project, fi: FuncInfo
                        ) -> List[Finding]:
        out: List[Finding] = []
        # donating callables bound in this function: var -> (idx, why)
        donating: Dict[str, Tuple[int, str]] = {}
        for pname, idx in self.donating_params.items():
            if any(a.arg == pname for a in fi.node.args.args):
                donating[pname] = (idx, f"parameter `{pname}` is a "
                                        "donating callable by convention")
        # reservation vars: var -> producing line
        reservations: Dict[str, int] = {}
        # lease pairs: token var -> leased var
        leases: Dict[str, str] = {}
        # taints: var -> (event line, rule, why)
        taints: Dict[str, Tuple[int, str, str]] = {}
        # one finding per tainted name per use-line (`f(ps, ps.si)` is
        # one defect, not two)
        reported: set = set()

        nodes = self._ordered_nodes(fi)
        # calls that ARE an assignment's value are handled inside the
        # Assign branch (taint from the call must land BEFORE the
        # target rebind clears it: `carry = g(carry, x)` is clean)
        assign_values = {id(n.value) for n in nodes
                         if isinstance(n, ast.Assign)}
        for node in nodes:
            if isinstance(node, ast.Assign):
                if isinstance(node.value, ast.Call):
                    self._track_call(project, fi, node.value, donating,
                                     reservations, leases, taints, out)
                # re-binding clears taint / updates tracking
                names = self._target_names(node.targets)
                for n in names:
                    taints.pop(n, None)
                self._track_assign(project, fi, node, names, donating,
                                   reservations, leases)
            elif isinstance(node, ast.Call) and id(node) not in assign_values:
                self._track_call(project, fi, node, donating, reservations,
                                 leases, taints, out)
            elif isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load) and node.id in taints:
                line, rule, why = taints[node.id]
                if node.lineno > line \
                        and (node.id, node.lineno) not in reported:
                    reported.add((node.id, node.lineno))
                    out.append(project.finding(
                        self.pass_id, rule, fi, node,
                        f"`{node.id}` used after {why} (line {line}): "
                        "the buffers may already be deleted or owned "
                        "elsewhere"))
        return out

    def _ordered_nodes(self, fi: FuncInfo):
        nodes = [n for n in iter_scope(fi.node)]
        nodes.sort(key=lambda n: (getattr(n, "lineno", 0),
                                  getattr(n, "col_offset", 0)))
        return nodes

    def _target_names(self, targets) -> List[str]:
        names: List[str] = []
        for t in targets:
            if isinstance(t, ast.Name):
                names.append(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                names.extend(self._target_names(t.elts))
        return names

    def _track_assign(self, project: Project, fi: FuncInfo,
                      node: ast.Assign, names: List[str],
                      donating, reservations, leases) -> None:
        value = node.value
        if not isinstance(value, ast.Call):
            return
        canon = project.canonical(fi.module, value.func) or ""
        tail = canon.rsplit(".", 1)[-1]
        # jax.jit(f, donate_argnums=(..)) -> donating callable
        if canon in ("jax.jit", "jit") and names:
            for kw in value.keywords:
                if kw.arg == "donate_argnums":
                    idx = self._first_index(kw.value)
                    if idx is not None:
                        donating[names[0]] = (
                            idx, f"jax.jit(donate_argnums) at line "
                                 f"{node.lineno}")
        # build_packed_chain(...) et al
        elif tail in self.builders and names:
            idx, gate = self.builders[tail]
            if gate is not None:
                for kw in value.keywords:
                    if kw.arg == gate and isinstance(
                            kw.value, ast.Constant) \
                            and kw.value.value is False:
                        return
            donating[names[0]] = (
                idx, f"`{tail}()` result donates argument {idx}")
        # r = batcher.reserve(...) / Reservation(...)
        elif tail in _RESERVE_PRODUCERS and names:
            reservations[names[0]] = node.lineno
        # ps, token = mgr.lease_packed()
        elif tail in _LEASE_METHODS and len(names) == 2:
            leases[names[1]] = names[0]

    def _track_call(self, project: Project, fi: FuncInfo, call: ast.Call,
                    donating, reservations, leases, taints, out) -> None:
        func = call.func
        # donated call: g(a0, a1, ...) where g is a donating callable
        if isinstance(func, ast.Name) and func.id in donating:
            idx, why = donating[func.id]
            if idx < len(call.args):
                arg = call.args[idx]
                if isinstance(arg, ast.Name):
                    taints[arg.id] = (
                        getattr(call, "end_lineno", call.lineno), "DN001",
                        f"being donated to `{func.id}` ({why})")
        if not isinstance(func, ast.Attribute):
            return
        base = func.value
        base_name = base.id if isinstance(base, ast.Name) else None
        # reservation close: r.commit() / r.abort()
        if func.attr in _CLOSE_METHODS and base_name in reservations:
            if fi.cls in self.exempt_classes:
                return
            taints[base_name] = (
                getattr(call, "end_lineno", call.lineno), "DN003",
                f"`.{func.attr}()` closed the reservation")
        # lease close: mgr.commit_packed(..., lease_token=token)
        elif func.attr == "commit_packed":
            for kw in call.keywords:
                if kw.arg == "lease_token" and isinstance(
                        kw.value, ast.Name) and kw.value.id in leases:
                    leased = leases[kw.value.id]
                    taints[leased] = (
                        getattr(call, "end_lineno", call.lineno), "DN002",
                        "the lease it was obtained under was committed "
                        f"(lease_token=`{kw.value.id}`)")

    def _first_index(self, node: ast.AST) -> Optional[int]:
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return node.value
        if isinstance(node, (ast.Tuple, ast.List)) and node.elts:
            first = node.elts[0]
            if isinstance(first, ast.Constant) and isinstance(
                    first.value, int):
                return first.value
        return None


__all__ = ["DonationPass", "PASS_ID", "DEFAULT_DONATING_BUILDERS",
           "DEFAULT_DONATING_PARAMS"]
