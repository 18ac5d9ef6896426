"""Layer 1 of the determinism auditor: jaxpr-level contract checking.

``audit(fn, example_args, rules)`` traces ``fn`` with ``jax.make_jaxpr`` and
walks the resulting ClosedJaxpr — recursing into every sub-jaxpr a primitive
carries (``jit``, ``while``, ``scan``, ``cond``, ``custom_jvp``/``vjp``,
``pallas_call``, remat) — while propagating value-level *labels* that the
rules in ``analysis/rules.py`` consume.  The walker is rule-agnostic: it
computes the label environment; rules are sink checkers over (eqn, labels).

Label semantics (what the abstract interpretation tracks)
---------------------------------------------------------
The padded selector programs (``core/space.pad_to``) right-pad the candidate
axis M; the contract is that padding lanes never influence a decision.  Taint
*reachability* alone cannot check that — nearly every value is reachable from
the observation state, including buggy unmasked reduces — so each value
carries a polarity label:

* ``MASK``     — boolean, guaranteed **False on padding lanes** (the
  ``valid`` mask itself, the observation/censor state rows whose padding
  tail is never written, and any AND-chain containing one of them);
* ``ANTIMASK`` — boolean, guaranteed **True on padding lanes** (``~mask``):
  selecting *through* it re-admits padding, so it never satisfies a reduce;
* ``CLEAN``    — data whose padding entries are neutralized (constants, or
  the result of ``where(mask, x, neutral)`` / ``mask * x`` patterns);
* ``DIRTY``    — no guarantee (the default; model outputs such as mu/sigma
  are DIRTY until re-masked).

Two auxiliary flags ride along: ``QUANT`` (value passed through the
``quantize_scores`` bit pattern — ``bitcast→add→and→bitcast``) and
``SELIDX`` (index produced by an argmax over masked scores, or by a min
over ``where(pred, iota, fill)``, so provably a non-padding index;
``iota == SELIDX`` one-hot compares therefore yield MASK, which is how the episode bodies' scatter masks stay clean through the
``while`` fixpoint).

Loops are handled by iterating the body's transfer function until the carry
labels stabilize (labels only ever degrade toward DIRTY, so the fixpoint is
reached in a handful of passes).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable

import jax
import numpy as np
from jax.extend import core as jcore

__all__ = ["Finding", "Labels", "Rule", "audit", "audit_jaxpr",
           "program_signature", "signature"]


# --------------------------------------------------------------------------- #
# Findings and labels
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation located in a traced program."""

    rule: str                   # rule id, e.g. "R1"
    primitive: str              # offending primitive name
    message: str                # human-readable explanation
    path: tuple[str, ...] = ()  # sub-jaxpr context, e.g. ("jit:jaxpr", "while:body")
    program: str = ""           # registry program name (filled by audit_all)

    def __str__(self):
        where = "/".join(self.path) or "<top>"
        prog = f"{self.program}: " if self.program else ""
        return f"[{self.rule}] {prog}{where}: {self.primitive}: {self.message}"


# Polarity lattice: DIRTY is bottom-of-trust; join degrades toward DIRTY.
DIRTY, MASK, ANTIMASK, CLEAN = "dirty", "mask", "antimask", "clean"
_CLEANISH = (MASK, CLEAN)


@dataclasses.dataclass(frozen=True)
class Labels:
    """Abstract value attached to each jaxpr variable."""

    pol: str = DIRTY
    quant: bool = False
    selidx: bool = False
    iota_axes: tuple[int, ...] = ()   # axes this value is an iota over
    splat: bool = False               # one scalar broadcast to every lane

    @property
    def cleanish(self) -> bool:
        return self.pol in _CLEANISH


_DIRTY = Labels()


def _join(a: Labels, b: Labels) -> Labels:
    """Lattice join used when control paths merge (loop carries, cond)."""
    if a.pol == b.pol:
        pol = a.pol
    elif {a.pol, b.pol} <= set(_CLEANISH):
        pol = CLEAN                      # mask joined with clean data: clean
    else:
        pol = DIRTY
    return Labels(pol=pol, quant=a.quant and b.quant,
                  selidx=a.selidx and b.selidx,
                  iota_axes=tuple(set(a.iota_axes) & set(b.iota_axes)),
                  splat=a.splat and b.splat)


# --------------------------------------------------------------------------- #
# Primitive classes
# --------------------------------------------------------------------------- #
# Shape-only ops: labels pass straight through (axis bookkeeping for iota is
# handled conservatively — only broadcast_in_dim/reshape keep iota axes).
_PASSTHROUGH = {
    "reshape", "broadcast_in_dim", "transpose", "slice", "squeeze",
    "expand_dims", "rev", "copy", "stop_gradient", "reduce_precision",
    "convert_element_type",
}
_GATHER = {"gather", "dynamic_slice", "take", "take_along_axis"}
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin"}
_ELEMENTWISE_CMP = {"eq", "ne", "lt", "le", "gt", "ge"}
# Sub-jaxpr parameter names by primitive (searched in this order).
_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                    "branches", "fwd_jaxpr_thunk")


def _literal(v) -> bool:
    return isinstance(v, jcore.Literal)


def _subjaxprs(eqn) -> list[tuple[str, Any]]:
    """(tag, ClosedJaxpr/Jaxpr) pairs hanging off an eqn's params."""
    out = []
    for k in _SUBJAXPR_PARAMS:
        v = eqn.params.get(k)
        if v is None:
            continue
        if isinstance(v, (tuple, list)):
            out.extend((f"{eqn.primitive.name}:{k}[{i}]", b)
                       for i, b in enumerate(v)
                       if isinstance(b, (jcore.ClosedJaxpr, jcore.Jaxpr)))
        elif isinstance(v, (jcore.ClosedJaxpr, jcore.Jaxpr)):
            out.append((f"{eqn.primitive.name}:{k}", v))
    return out


def _as_jaxpr(j):
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


# --------------------------------------------------------------------------- #
# The walker
# --------------------------------------------------------------------------- #
class Rule:
    """Base class for jaxpr rules (see analysis/rules.py).

    ``mask_argnums`` / ``clean_argnums`` seed the polarity labels at the
    program's flat argument positions; ``check_eqn`` is called on every
    equation (including inside sub-jaxprs) with the current label
    environment and must return an iterable of :class:`Finding`.
    """

    id = "R?"
    mask_argnums: tuple[int, ...] = ()
    clean_argnums: tuple[int, ...] = ()

    def check_eqn(self, eqn, get: Callable[[Any], Labels],
                  path: tuple[str, ...]) -> Iterable[Finding]:
        return ()

    def check_jaxpr(self, jaxpr, path: tuple[str, ...]) -> Iterable[Finding]:
        return ()


class _Auditor:
    def __init__(self, rules: list[Rule]):
        self.rules = rules
        self.findings: list[Finding] = []

    # -- label transfer ---------------------------------------------------- #
    def _transfer(self, eqn, env: dict) -> list[Labels]:
        """Output labels of one eqn given the input label environment."""
        prim = eqn.primitive.name

        def get(v) -> Labels:
            if _literal(v):
                return Labels(pol=CLEAN)
            return env.get(v, _DIRTY)

        ins = [get(v) for v in eqn.invars]
        arrays = [lab for v, lab in zip(eqn.invars, ins)
                  if not _literal(v) and getattr(v.aval, "shape", ()) != ()]

        if prim == "iota":
            return [Labels(pol=CLEAN, iota_axes=(eqn.params["dimension"],))]
        if prim in _PASSTHROUGH:
            lab = ins[0]
            if prim not in ("reshape", "broadcast_in_dim",
                            "convert_element_type"):
                lab = dataclasses.replace(lab, iota_axes=())
            elif prim == "broadcast_in_dim" and lab.iota_axes:
                dims = eqn.params["broadcast_dimensions"]
                lab = dataclasses.replace(
                    lab, iota_axes=tuple(dims[a] for a in lab.iota_axes
                                         if a < len(dims)))
            if prim == "broadcast_in_dim":
                lab = dataclasses.replace(
                    lab, splat=not getattr(eqn.invars[0].aval, "shape", ()))
            return [lab]
        if prim in _GATHER:
            return [dataclasses.replace(ins[0], iota_axes=())]
        if prim == "not":
            pol = ins[0].pol
            flip = {MASK: ANTIMASK, ANTIMASK: MASK}.get(pol, pol)
            return [Labels(pol=flip)]
        if prim == "and":
            pols = [l.pol for l in ins]
            if MASK in pols:
                return [Labels(pol=MASK)]
            if all(p == ANTIMASK for p in pols):
                return [Labels(pol=ANTIMASK)]
            if all(l.cleanish for l in ins):
                return [Labels(pol=CLEAN, quant=any(l.quant for l in ins))]
            return [Labels(quant=any(l.quant for l in ins))]
        if prim == "or":
            pols = [l.pol for l in ins]
            if ANTIMASK in pols:
                return [Labels(pol=ANTIMASK)]
            if all(p == MASK for p in pols):
                return [Labels(pol=MASK)]
            return [_DIRTY]
        if prim == "mul":
            # Only an operand that is zero/False at padding cleans a product:
            # a mask, or a CLEAN *array* (whose padding entries are the
            # masking neutral).  A CLEAN scalar (literal, reduced mean)
            # broadcasts the same value onto padding lanes and cleans
            # nothing.
            def _zeroes_padding(v, l):
                return l.pol == MASK or (
                    l.pol == CLEAN and not _literal(v)
                    and getattr(v.aval, "shape", ()) != ())
            if any(_zeroes_padding(v, l) for v, l in zip(eqn.invars, ins)):
                return [Labels(pol=CLEAN)]
            return [_DIRTY]
        if prim == "select_n":
            pred, cases = ins[0], ins[1:]
            if pred.pol == MASK:
                ok = cases[0].cleanish    # padding -> False -> case 0
            elif pred.pol == ANTIMASK:
                ok = cases[-1].cleanish   # padding -> True -> last case
            else:
                ok = all(c.cleanish for c in cases)
            pol = CLEAN if ok else DIRTY
            if pol == CLEAN and all(c.pol == MASK for c in cases):
                pol = MASK                # merging two masks stays a mask
            # where(pred, iota, fill): an iota with some lanes filled by a
            # scalar, whose min is the lowest lane where pred holds.
            iotas = {c.iota_axes for c in cases if not c.splat}
            iota_axes = iotas.pop() if len(iotas) == 1 else ()
            return [Labels(pol=pol, quant=any(c.quant for c in cases),
                           iota_axes=iota_axes)]
        if prim in _ELEMENTWISE_CMP:
            # iota(m) == selection-index: a one-hot of a provably non-padding
            # index — False on every padding lane.
            if prim == "eq" and len(ins) == 2:
                a, b = ins
                if (a.iota_axes and b.selidx) or (b.iota_axes and a.selidx):
                    return [Labels(pol=MASK)]
            return [_DIRTY]
        if prim in ("argmax", "argmin"):
            src = ins[0]
            return [Labels(pol=DIRTY,
                           selidx=bool(src.quant or src.cleanish))]
        if prim == "reduce_min" and ins[0].iota_axes and set(
                eqn.params["axes"]) <= set(ins[0].iota_axes):
            # min over where(score == top, iota, fill): the lowest-index
            # argmax, a selection index like argmax's own.
            return [Labels(pol=DIRTY, selidx=True)]
        if prim in _REDUCE or prim == "dot_general":
            return [_DIRTY for _ in eqn.outvars]
        if prim == "concatenate":
            pol = CLEAN if all(l.cleanish for l in arrays or ins) else DIRTY
            if arrays and all(l.pol == MASK for l in arrays):
                pol = MASK
            return [Labels(pol=pol, quant=all(l.quant for l in arrays or ins))]
        if prim in ("max", "min"):
            # clamp of a selection index against a literal stays an index
            selidx = any(l.selidx for l in ins) and all(
                l.selidx or _literal(v) or getattr(v.aval, "shape", ()) == ()
                for v, l in zip(eqn.invars, ins))
            pol = CLEAN if all(l.cleanish for l in ins) else DIRTY
            return [Labels(pol=pol, selidx=selidx)]
        if prim == "bitcast_convert_type":
            lab = ins[0]
            return [dataclasses.replace(lab, iota_axes=())]
        if prim == "get":
            # Pallas ref read: the value carries the ref's current label.
            return [dataclasses.replace(ins[0], iota_axes=())]
        if prim in ("swap", "addupdate"):
            # Pallas ref write: fold the stored value's label into the ref
            # (env mutation — refs are invars, so later reads see it).  The
            # join, not an overwrite, keeps multi-write kernels sound.
            ref = eqn.invars[0]
            old = ins[0]
            if not _literal(ref):
                env[ref] = _join(old, ins[1])
            return [old for _ in eqn.outvars]
        # generic: elementwise-ish default — clean iff every array input is
        # clean; anything structural we don't model degrades to DIRTY.
        if arrays and all(l.cleanish for l in arrays):
            return [Labels(pol=CLEAN) for _ in eqn.outvars]
        return [_DIRTY for _ in eqn.outvars]

    # -- quantize_scores pattern ------------------------------------------- #
    def _mark_quantize(self, eqn, env, producers) -> bool:
        """Detect the closing bitcast of the quantize_scores bit pattern:
        ``bitcast(f32->u32) -> add -> and -> bitcast(u32->f32)``."""
        if eqn.primitive.name != "bitcast_convert_type":
            return False
        if np.dtype(eqn.params.get("new_dtype")) != np.dtype("float32"):
            return False
        chain = ("and", "add", "bitcast_convert_type")
        v = eqn.invars[0]
        for want in chain:
            if _literal(v):
                return False
            prod = producers.get(v)
            if prod is None or prod.primitive.name != want:
                return False
            nxt = [iv for iv in prod.invars
                   if not _literal(iv) and iv in producers or
                   (not _literal(iv) and want == "bitcast_convert_type")]
            v = nxt[0] if nxt else (prod.invars[0]
                                    if not _literal(prod.invars[0]) else None)
            if v is None and want != "bitcast_convert_type":
                return False
        return True

    # -- jaxpr walk --------------------------------------------------------- #
    def walk(self, jaxpr, in_labels: list[Labels],
             path: tuple[str, ...]) -> list[Labels]:
        """Propagate labels through one (sub-)jaxpr; returns outvar labels."""
        jaxpr = _as_jaxpr(jaxpr)
        env: dict = {}
        for v, lab in zip(jaxpr.invars, in_labels):
            env[v] = lab
        for v in jaxpr.constvars:
            env[v] = Labels(pol=CLEAN)
        producers: dict = {}

        for rule in self.rules:
            self.findings.extend(rule.check_jaxpr(jaxpr, path))

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name

            def get(v, _env=env):
                if _literal(v):
                    return Labels(pol=CLEAN)
                return _env.get(v, _DIRTY)

            subs = _subjaxprs(eqn)
            if subs and prim in ("jit", "closed_call", "core_call",
                                 "custom_jvp_call", "custom_vjp_call",
                                 "remat", "checkpoint", "custom_vmap_call"):
                tag, sub = subs[0]
                ins = [get(v) for v in eqn.invars]
                outs = self.walk(sub, ins, path + (tag,))
                outs = list(outs) + [_DIRTY] * (len(eqn.outvars) - len(outs))
                for v, lab in zip(eqn.outvars, outs):
                    env[v] = lab
                    producers[v] = eqn
                continue
            if prim == "while":
                cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
                ins = [get(v) for v in eqn.invars]
                cond_consts = ins[:cn]
                body_consts = ins[cn:cn + bn]
                carry = list(ins[cn + bn:])
                body = eqn.params["body_jaxpr"]
                cond = eqn.params["cond_jaxpr"]
                for _ in range(8):                      # fixpoint on labels
                    snapshot = list(carry)
                    outs = self.walk(body, body_consts + carry,
                                     path + ("while:body",), quiet=True)
                    carry = [_join(a, b) for a, b in zip(carry, outs)]
                    if carry == snapshot:
                        break
                # final audited pass at the fixpoint labels
                self.walk(cond, cond_consts + carry, path + ("while:cond",))
                self.walk(body, body_consts + carry, path + ("while:body",))
                for v, lab in zip(eqn.outvars, carry):
                    env[v] = lab
                    producers[v] = eqn
                continue
            if prim == "scan":
                nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
                ins = [get(v) for v in eqn.invars]
                consts, carry = ins[:nc], list(ins[nc:nc + ncar])
                xs = [dataclasses.replace(l, iota_axes=())
                      for l in ins[nc + ncar:]]
                body = eqn.params["jaxpr"]
                for _ in range(8):
                    snapshot = list(carry)
                    outs = self.walk(body, consts + carry + xs,
                                     path + ("scan:body",), quiet=True)
                    carry = [_join(a, b) for a, b in zip(carry, outs[:ncar])]
                    if carry == snapshot:
                        break
                outs = self.walk(body, consts + carry + xs,
                                 path + ("scan:body",))
                outs = carry + list(outs[ncar:])
                for v, lab in zip(eqn.outvars, outs):
                    env[v] = lab
                    producers[v] = eqn
                continue
            if prim == "cond":
                ins = [get(v) for v in eqn.invars]
                branch_outs = []
                for i, (tag, br) in enumerate(subs):
                    branch_outs.append(self.walk(br, ins[1:], path + (tag,)))
                outs = branch_outs[0]
                for other in branch_outs[1:]:
                    outs = [_join(a, b) for a, b in zip(outs, other)]
                for v, lab in zip(eqn.outvars, outs):
                    env[v] = lab
                    producers[v] = eqn
                continue
            if prim == "pallas_call" and subs:
                # Kernel jaxpr invars are refs ordered [index operands,
                # inputs, outputs, scratch]; eqn.invars cover the first two
                # groups, so their labels seed the in-refs directly.  Out
                # and scratch refs start at the lattice top (every flag
                # optimistic) and the grid fixpoint below degrades them to
                # whatever the body actually stores (``swap`` joins into the
                # ref's env entry) — later grid invocations then re-read the
                # stabilized labels, exactly like the while-loop carry.
                tag, sub = subs[0]
                inner = _as_jaxpr(sub)
                ins = [get(v) for v in eqn.invars]
                n_data = len(ins)
                top = Labels(pol=MASK, quant=True, selidx=True)
                seed = ins + [top] * (len(inner.invars) - n_data)
                for _ in range(8):
                    snapshot = list(seed)
                    self.walk(sub, seed, path + (tag,), quiet=True)
                    envb = self._last_env
                    final = [envb.get(v, lab)
                             for v, lab in zip(inner.invars, seed)]
                    seed = (seed[:n_data]
                            + [_join(a, b) for a, b in
                               zip(seed[n_data:], final[n_data:])])
                    if seed == snapshot:
                        break
                self.walk(sub, seed, path + (tag,))
                envb = self._last_env
                out_refs = inner.invars[n_data:n_data + len(eqn.outvars)]
                for v, rv in zip(eqn.outvars, out_refs):
                    env[v] = dataclasses.replace(
                        envb.get(rv, _DIRTY), iota_axes=())
                    producers[v] = eqn
                for v in eqn.outvars[len(out_refs):]:
                    env[v] = _DIRTY
                    producers[v] = eqn
                for rule in self.rules:
                    self.findings.extend(rule.check_eqn(eqn, get, path))
                continue
            if subs:                                    # unmodeled callers
                for tag, sub in subs:
                    inner = _as_jaxpr(sub)
                    self.walk(sub, [_DIRTY] * len(inner.invars), path + (tag,))
                for v in eqn.outvars:
                    env[v] = _DIRTY
                    producers[v] = eqn
                for rule in self.rules:
                    self.findings.extend(rule.check_eqn(eqn, get, path))
                continue

            for rule in self.rules:
                self.findings.extend(rule.check_eqn(eqn, get, path))

            outs = self._transfer(eqn, env)
            if self._mark_quantize(eqn, env, producers):
                outs = [dataclasses.replace(outs[0], quant=True)]
            for v, lab in zip(eqn.outvars, outs):
                env[v] = lab
                producers[v] = eqn

        self._last_env = env        # pallas_call reads back final ref labels
        return [Labels(pol=CLEAN) if _literal(v) else env.get(v, _DIRTY)
                for v in jaxpr.outvars]

    # quiet passes (fixpoint iterations) must not duplicate findings
    def _walk_quiet(self, *a, **k):
        saved, self.findings = self.findings, []
        try:
            out = self.walk(*a, **k)
        finally:
            self.findings = saved
        return out


# Give walk() a quiet= keyword without threading it through every call site.
_Auditor._walk_impl = _Auditor.walk


def _walk(self, jaxpr, in_labels, path, quiet=False):
    if quiet:
        return self._walk_quiet(jaxpr, in_labels, path)
    return self._walk_impl(jaxpr, in_labels, path)


_Auditor.walk = _walk


# --------------------------------------------------------------------------- #
# Entry points
# --------------------------------------------------------------------------- #
def audit_jaxpr(closed: jcore.ClosedJaxpr, rules: list[Rule],
                program: str = "") -> list[Finding]:
    """Run ``rules`` over an already-traced ClosedJaxpr."""
    auditor = _Auditor(list(rules))
    n_in = len(closed.jaxpr.invars)
    labels = [_DIRTY] * n_in
    for rule in rules:
        for i in rule.mask_argnums:
            labels[i] = Labels(pol=MASK)
        for i in rule.clean_argnums:
            labels[i] = Labels(pol=CLEAN)
    auditor.walk(closed, labels, ())
    if program:
        return [dataclasses.replace(f, program=program)
                for f in auditor.findings]
    return auditor.findings


def audit(fn, example_args: tuple, rules: list[Rule], *,
          example_kwargs: dict | None = None,
          program: str = "") -> list[Finding]:
    """Trace ``fn`` on example arguments and audit the traced program.

    ``example_args`` are flattened exactly the way ``jax.make_jaxpr``
    flattens them, so a rule's ``mask_argnums``/``clean_argnums`` index into
    the flat argument list (see ``registry.flat_argnums`` for a helper that
    turns pytree paths into flat positions).
    """
    closed = jax.make_jaxpr(fn)(*example_args, **(example_kwargs or {}))
    return audit_jaxpr(closed, rules, program=program)


# --------------------------------------------------------------------------- #
# Canonical program signatures (pretty-print-drift-resilient jaxpr identity)
# --------------------------------------------------------------------------- #
def _render_aval(aval) -> str:
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    name = getattr(dtype, "name", str(dtype))
    return f"{name}{list(shape)}" if shape is not None else str(aval)


def _render_param(v) -> str:
    if isinstance(v, (jcore.ClosedJaxpr, jcore.Jaxpr)):
        return "{" + program_signature(v) + "}"
    if isinstance(v, (tuple, list)):
        return "[" + ",".join(_render_param(x) for x in v) + "]"
    if callable(v) and not isinstance(v, type):
        return getattr(v, "__name__", "fn")
    return repr(v)


def program_signature(jaxpr) -> str:
    """A canonical, stable rendering of a (Closed)Jaxpr.

    Variables are renamed to dense indices in definition order and parameters
    are rendered through our own formatter, so two traces compare equal iff
    they are the same program — regardless of how a given jax version
    pretty-prints jaxprs (the brittle thing ``str(jaxpr)`` pins pick up).
    Cosmetic params (``name``) are dropped.
    """
    jaxpr = _as_jaxpr(jaxpr)
    names: dict = {}

    def nm(v):
        if _literal(v):
            return f"lit({v.val!r}:{_render_aval(v.aval)})"
        if v not in names:
            names[v] = f"v{len(names)}"
        return f"{names[v]}:{_render_aval(v.aval)}"

    lines = ["in(" + ",".join(nm(v) for v in
                              list(jaxpr.constvars) + list(jaxpr.invars)) + ")"]
    for eqn in jaxpr.eqns:
        params = ",".join(
            f"{k}={_render_param(v)}" for k, v in sorted(eqn.params.items())
            if k not in ("name", "sharding"))
        lines.append(
            f"{eqn.primitive.name}[{params}]("
            + ",".join(nm(v) for v in eqn.invars) + ")->("
            + ",".join(nm(v) for v in eqn.outvars) + ")")
    lines.append("out(" + ",".join(nm(v) for v in jaxpr.outvars) + ")")
    return "\n".join(lines)


def signature(fn, *example_args, **example_kwargs) -> str:
    """Trace ``fn`` and return its canonical program signature."""
    return program_signature(jax.make_jaxpr(fn)(*example_args,
                                                **example_kwargs))
