"""Device time of the window by scope of the segment program: the profiler
trace's operations (``ctx["trace"]["ops"]``, device self seconds by full
HLO instruction text) joined to the program's own account of what it
compiled (``repro.obs.segment_program_text``: every instruction with its
result shape and ``op_name`` metadata).

An instruction's scope is the deepest scope of the vocabulary below on its
``op_name`` path (``jit(_episode_segment)/lynceus/segment/while/body/...
/vmap(fit)/vmap(bootstrap)/...``; a transform's wrapper such as
``vmap(...)`` is looked through).  A fusion carries its root's
``op_name``, as XLA sets it.  Instruction names are unique within one
module only, and the window also runs the host path's small programs: an
operation whose name is in the segment program but whose result shape
differs there, or whose name is not there at all, is ``unscoped``.

The join is made once per run and kept in ``ctx``.  A program without
``segment_program_text`` declares no scopes: nothing of its device time is
attributed to one (:func:`seconds` returns None and the readers read 0
for a scope, 100 for ``unscoped``).
"""

import re
import sys
import time

# The yardstick's copy of the program's vocabulary (repro.obs.SCOPES; a
# CPU test holds the two equal).  A scope below another is entered inside
# its parent.
SCOPES = ("lynceus", "segment", "speculate", "fit", "fit/bootstrap",
          "fit/split_stats", "fit/split_choice", "fit/route", "select_step")
UNSCOPED = "unscoped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_COMMENT = re.compile(r"/\*.*?\*/")


def _part(component: str) -> str:
    """A path component with its transform wrappers taken off:
    ``vmap(vmap(fit))`` → ``fit``."""
    while True:
        m = _WRAPPED.match(component)
        if not m:
            return component
        component = m.group(1)


def scope_of(op_name: str) -> str | None:
    """The deepest vocabulary scope on an ``op_name`` path, or None."""
    found, best = set(), None
    for part in map(_part, op_name.split("/")):
        for sc in SCOPES:
            parent, _, leaf = sc.rpartition("/")
            if leaf == part and (not parent or parent in found):
                found.add(sc)
                best = sc
    return best


def result_shape(rest: str) -> str | None:
    """The result shape of an instruction's text after ``name = ``, with
    its layouts and comments taken off (``f32[8,384]``, ``(f32[8],
    s32[8])``); None when the text carries none."""
    rest = _COMMENT.sub("", rest).strip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                shape = rest[:i + 1]
                break
        else:
            return None
    else:
        shape = rest.split(" ", 1)[0]
    while _LAYOUT.search(shape):
        shape = _LAYOUT.sub("", shape)
    return shape if "[" in shape else None


def program_map(text: str) -> dict:
    """Instruction name → (scope or None, result shape) over every
    instruction of an HLO module's text that carries an ``op_name``."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(m.group(2))
        if op:
            out[m.group(1)] = (scope_of(op.group(1)),
                               result_shape(m.group(2)))
    return out


def attribute(ops: dict, pmap: dict) -> dict:
    """Seconds per scope (every vocabulary scope, and ``unscoped``) of the
    trace's ``ops`` (full instruction text → device self seconds)."""
    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    for full, sec in ops.items():
        short, _, rest = full.partition(" = ")
        hit = pmap.get(short.strip().lstrip("%"))
        sc = UNSCOPED
        if hit and hit[0] and hit[1] is not None \
                and result_shape(rest) == hit[1]:
            sc = hit[0]
        out[sc] += sec
    return out


def _program_text(ctx) -> str | None:
    try:
        from repro.obs import segment_program_text
    except ImportError:
        return None
    svc = ctx["svc"]
    t0 = time.perf_counter()
    text = segment_program_text(svc.program_jobs, svc.settings,
                                svc.service_config)
    print(f"bench: segment_program_text {time.perf_counter() - t0!r} s",
          file=sys.stderr, flush=True)
    return text


def seconds(ctx):
    """Seconds per scope of the window (:func:`attribute`), joined once per
    run; None when the program declares no scopes.  An empty dict when
    there is no trace or the program's text carries no ``op_name``."""
    if "scope_seconds" not in ctx:
        tr = ctx.get("trace")
        text = _program_text(ctx) if tr else ""
        if text is None:
            ctx["scope_seconds"] = None
        else:
            pmap = program_map(text)
            ctx["scope_seconds"] = attribute(tr["ops"], pmap) \
                if pmap else {}
    return ctx["scope_seconds"]


def share(ctx, scope: str):
    """Percent of the device's busy time in ``scope`` and every scope below
    it; 0 for a program that declares no scopes; None when nothing of it
    ran or there is nothing to read."""
    tr = ctx.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    sec = seconds(ctx)
    if sec is None:
        return 0.0
    total = sum(v for k, v in sec.items()
                if k == scope or k.startswith(scope + "/"))
    return 100.0 * total / tr["busy_s"] if total > 0 else None
