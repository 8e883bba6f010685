#!/usr/bin/env python
"""apexlint: invariant-aware static analysis for the tpu-apex fleet.

The repo's hard-won invariants — the tick_keys PRNG stream contract
(ISSUE 4/7), donated-buffer discipline in the fused scans, single-owner
drain boundaries (ISSUE 5), the REPLAY_FIELDS/provenance wire schema
(ISSUE 8), and the TPU_APEX_* knob surface — are enforced at *runtime*
by the RetraceDetector, TransferAudit, ingest quarantine and the parity
oracles.  A violation therefore costs a full fleet run to surface.
This tool is the *diff-time* twin: a pure-stdlib ``ast`` rule engine
(no jax import — it must run inside tier-1's budget on the 2-vCPU
image) that catches the same bug classes before they ship.

Rules (``--list-rules`` prints this catalog):

- ``donation-after-use`` — a buffer passed at a donated position of a
  ``jax.jit(..., donate_argnums=...)`` program is referenced again
  after the dispatch.  Donated buffers are *invalidated*: the reference
  silently aliases freed device memory (or raises on TPU).
- ``rng-key-reuse`` — the same PRNG key reaches two consuming draws
  (``jax.random.<sampler>`` or ``split``) without an interleaving
  rebind, or a ``PRNGKey`` is minted from a literal constant seed
  outside ``utils/rngs.py`` — both break the root-seed / tick_keys
  stream contract (streams must derive from the run seed via stable
  folds, and a key is use-once).
- ``retrace-hazard`` — a Python scalar that changes per iteration (the
  loop induction variable, or a host counter bumped in the loop) flows
  into a registered jitted program, or a non-hashable literal is passed
  at a ``static_argnums`` position: the static twin of the runtime
  RetraceDetector (every such call retraces = recompiles on the hot
  path).
- ``single-owner`` — a mutating method of a single-owner class
  (``drain``/``ring_write*``/quarantine ``put``) is invoked from a
  module that is not in the owner set the class declares via its
  ``__apex_mutators__``/``__apex_owner__`` annotations.
- ``schema-contract`` — positional indexing into ``Transition``/
  ``Segment`` rows, re-typed copies of the REPLAY_FIELDS tuple
  (shadow schemas drift silently), ``._fields`` used where the
  six-column replay schema is meant, and savez wire columns that
  drift from the module's declared ``WIRE_COLUMNS``.
- ``knob-registry`` — every ``TPU_APEX_*``/``*_FAULTS`` env read must
  be declared in ``config.KNOBS`` and documented in README.md and
  TESTING.md; declared knobs must still be read somewhere.  Drift in
  either direction is a finding.

Generic pass (same runner, ``--rules gen`` selects just these):

- ``unused-import`` / ``undefined-name`` / ``shadowed-builtin`` — the
  pyflakes-class hygiene checks, scope-aware.

Findings print as ``file:line · RULE_ID · message · hint: ...``; known
findings live in a checked-in baseline (``tools/apexlint_baseline.json``
by convention) where every entry carries a written justification —
an empty justification is a hard error, and entries that no longer
match anything are ``baseline-stale`` findings so the file is pruned
forward.  Suppress a single line in code with
``# apexlint: ignore[rule-id]`` (bare ``ignore`` silences all rules).

Exit codes: 0 clean, 1 findings (or stale
baseline entries), 2 usage/config error.

Usage:
    python tools/apexlint.py pytorch_distributed_tpu tools
    python tools/apexlint.py --json --baseline tools/apexlint_baseline.json
    python tools/apexlint.py --write-baseline   # then fill justifications
"""

from __future__ import annotations

import argparse
import ast
import builtins
import fnmatch
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

# ---------------------------------------------------------------------------
# rule catalog
# ---------------------------------------------------------------------------

RULES: Dict[str, str] = {
    "donation-after-use":
        "buffer referenced after being donated to a jitted dispatch",
    "rng-key-reuse":
        "PRNG key consumed twice / literal-seed key outside utils.rngs",
    "retrace-hazard":
        "per-iteration Python scalar or unhashable static arg into a "
        "jitted program",
    "single-owner":
        "single-owner mutation invoked outside the declared owner set",
    "schema-contract":
        "positional/shadow replay schema access or wire-column drift",
    "knob-registry":
        "env knob not declared in config.KNOBS or missing from docs",
    "unused-import": "imported name is never used",
    "undefined-name": "name is not defined in any enclosing scope",
    "shadowed-builtin": "binding shadows a Python builtin",
    "parse-error": "file failed to parse",
}

GENERIC_RULES = ("unused-import", "undefined-name", "shadowed-builtin")

# Replay schema fallback when utils/experience.py is outside the scanned
# tree (e.g. linting tools/ alone); the scanned value wins when present.
DEFAULT_REPLAY_FIELDS = (  # apexlint: ignore[schema-contract]
    "state0", "action", "reward", "gamma_n", "state1", "terminal1")

# env knob name-space this repo owns (the knob-registry rule's scope)
KNOB_SCOPE = re.compile(r"(^TPU_APEX)|(_FAULTS($|_))")

_PRAGMA = re.compile(r"#\s*apexlint:\s*ignore(?:\[([a-z0-9_,\- ]+)\])?")

# PRNG derivation calls that do NOT consume a key (the tick_keys
# contract: the base key may be re-folded forever), vs consuming draws.
_KEY_PURE = {"fold_in", "tick_keys", "PRNGKey", "key", "key_data",
             "wrap_key_data", "asarray", "device_put", "array",
             "process_key", "clone"}
_KEY_PARAM = re.compile(r"(^|_)key$")

_SHADOW_BUILTINS = frozenset({
    "list", "dict", "set", "tuple", "str", "int", "float", "bool",
    "bytes", "type", "id", "input", "filter", "map", "sum", "min",
    "max", "len", "range", "object", "print", "vars", "next", "iter",
    "hash", "dir", "abs", "all", "any", "round", "sorted", "zip",
    "open", "eval", "exec", "compile", "format", "pow", "repr",
    "super", "property", "enumerate", "reversed", "slice", "frozenset",
    "bytearray", "complex", "divmod", "callable", "isinstance",
    "issubclass", "bin", "hex", "oct",
})

_BUILTIN_NAMES = frozenset(dir(builtins)) | {
    "__file__", "__name__", "__doc__", "__package__", "__spec__",
    "__loader__", "__builtins__", "__debug__", "__path__", "__class__",
}


# ---------------------------------------------------------------------------
# findings + baseline
# ---------------------------------------------------------------------------

@dataclass
class Finding:
    path: str          # root-relative, forward slashes
    line: int
    rule: str
    message: str
    hint: str
    context: str = ""  # dotted enclosing class/def scope — line-stable key

    def key(self) -> Tuple[str, str, str, str]:
        return (self.rule, self.path, self.context, self.message)

    def format(self) -> str:
        return (f"{self.path}:{self.line} · {self.rule} · {self.message}"
                f" · hint: {self.hint}")

    def to_json(self) -> dict:
        return {"path": self.path, "line": self.line, "rule": self.rule,
                "message": self.message, "hint": self.hint,
                "context": self.context}


class BaselineError(Exception):
    """Malformed baseline file — exit 2, never silently ignored."""


def load_baseline(path: str) -> List[dict]:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise BaselineError(f"cannot read baseline {path}: {e}")
    entries = data.get("entries")
    if not isinstance(entries, list):
        raise BaselineError(f"{path}: top-level 'entries' list required")
    for i, e in enumerate(entries):
        for k in ("rule", "path", "context", "message", "justification"):
            if k not in e:
                raise BaselineError(f"{path}: entry {i} missing '{k}'")
        if not str(e["justification"]).strip() or \
                "TODO" in str(e["justification"]):
            raise BaselineError(
                f"{path}: entry {i} ({e['rule']} at {e['path']}) has an "
                f"empty/TODO justification — every baselined finding "
                f"needs a written reason it is acceptable")
    return entries


# ---------------------------------------------------------------------------
# module model: parse once, share alias/symbol resolution across rules
# ---------------------------------------------------------------------------

class Module:
    def __init__(self, abspath: str, relpath: str, text: str):
        self.abspath = abspath
        self.path = relpath.replace(os.sep, "/")
        self.tree = ast.parse(text, filename=relpath)
        self.lines = text.splitlines()
        # dotted module name, e.g. pytorch_distributed_tpu.agents.actor
        mod = self.path[:-3] if self.path.endswith(".py") else self.path
        if mod.endswith("/__init__"):
            mod = mod[: -len("/__init__")]
        self.dotted = mod.replace("/", ".")
        self.is_init = self.path.endswith("__init__.py")
        # per-line pragma suppressions: line -> set of rules ({"*"} = all)
        self.pragmas: Dict[int, Set[str]] = {}
        for i, ln in enumerate(self.lines, 1):
            m = _PRAGMA.search(ln)
            if m:
                rules = m.group(1)
                self.pragmas[i] = (
                    {r.strip() for r in rules.split(",")} if rules
                    else {"*"})
        # import alias map: local name -> dotted origin
        self.imports: Dict[str, str] = {}
        # module-level constants: name -> literal value (str / str-tuple)
        self.constants: Dict[str, Any] = {}
        self._collect_top_level()

    def _collect_top_level(self) -> None:
        pkg = self.dotted.rsplit(".", 1)[0] if "." in self.dotted else ""
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    local = a.asname or a.name.split(".")[0]
                    self.imports[local] = a.asname and a.name or \
                        a.name.split(".")[0]
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:  # relative: resolve against our package
                    parts = self.dotted.split(".")
                    parts = parts[: len(parts) - node.level] or [pkg]
                    base = ".".join(parts + ([node.module]
                                             if node.module else []))
                for a in node.names:
                    if a.name == "*":
                        continue
                    self.imports[a.asname or a.name] = f"{base}.{a.name}"
        for node in self.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                val = _literal(node.value)
                if val is not None:
                    self.constants[node.targets[0].id] = val

    def resolve(self, node: ast.AST) -> str:
        """Dotted origin of a Name/Attribute chain (''  when opaque)."""
        parts: List[str] = []
        cur = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if not isinstance(cur, ast.Name):
            return ""
        head = self.imports.get(cur.id, cur.id)
        return ".".join([head] + list(reversed(parts)))

    def suppressed(self, line: int, rule: str) -> bool:
        tags = self.pragmas.get(line)
        return bool(tags) and ("*" in tags or rule in tags)


def _literal(node: ast.AST) -> Any:
    """Constant str/int/float, or tuple of constant strs, else None."""
    if isinstance(node, ast.Constant) and isinstance(
            node.value, (str, int, float)):
        return node.value
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                vals.append(e.value)
            else:
                return None
        return tuple(vals)
    return None


def _context_of(module: Module, target: ast.AST) -> str:
    """Dotted class/def scope containing ``target`` (line-stable
    baseline key)."""
    best: List[str] = []

    def walk(node: ast.AST, stack: List[str]) -> bool:
        if node is target:
            best[:] = stack
            return True
        for child in ast.iter_child_nodes(node):
            s = stack
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                s = stack + [child.name]
            if walk(child, s):
                return True
        return False

    walk(module.tree, [])
    return ".".join(best)


# ---------------------------------------------------------------------------
# ordered event stream: loads/stores/calls in approximate execution
# order, loop bodies twice (so iteration-crossing hazards surface)
# ---------------------------------------------------------------------------

def iter_events(body: List[ast.stmt]) -> List[Tuple[str, Any, int]]:
    events: List[Tuple[str, Any, int]] = []

    def expr(node: Optional[ast.AST]) -> None:
        if node is None:
            return
        if isinstance(node, ast.Name):
            kind = "store" if isinstance(node.ctx, ast.Store) else "load"
            events.append((kind, node.id, node.lineno))
            return
        if isinstance(node, ast.Call):
            expr(node.func)
            for a in node.args:
                expr(a)
            for kw in node.keywords:
                expr(kw.value)
            events.append(("call", node, node.lineno))
            return
        if isinstance(node, ast.Lambda):
            # closure loads happen "at" the def site, conservatively —
            # but only of FREE names: the lambda's own params are not
            # reads of the enclosing scope
            a = node.args
            params = {x.arg for x in (a.posonlyargs + a.args +
                                      a.kwonlyargs +
                                      ([a.vararg] if a.vararg else []) +
                                      ([a.kwarg] if a.kwarg else []))}
            for inner in ast.walk(node.body):
                if isinstance(inner, ast.Name) and isinstance(
                        inner.ctx, ast.Load) and inner.id not in params:
                    events.append(("load", inner.id, inner.lineno))
            return
        for child in ast.iter_child_nodes(node):
            expr(child)

    def assign_target(t: ast.AST) -> None:
        if isinstance(t, ast.Name):
            events.append(("store", t.id, t.lineno))
        else:
            expr(t)

    def stmt(s: ast.stmt) -> None:
        if isinstance(s, ast.Assign):
            expr(s.value)
            for t in s.targets:
                assign_target(t)
        elif isinstance(s, ast.AnnAssign):
            expr(s.value)
            if s.value is not None:
                assign_target(s.target)
        elif isinstance(s, ast.AugAssign):
            if isinstance(s.target, ast.Name):
                events.append(("load", s.target.id, s.lineno))
            expr(s.value)
            assign_target(s.target)
        elif isinstance(s, (ast.For, ast.AsyncFor)):
            expr(s.iter)
            # loop body twice: a use "before" the donating call in the
            # source still runs after it on the next iteration
            for _ in range(2):
                assign_target(s.target)
                for b in s.body:
                    stmt(b)
            for b in s.orelse:
                stmt(b)
        elif isinstance(s, ast.While):
            for _ in range(2):
                expr(s.test)
                for b in s.body:
                    stmt(b)
            for b in s.orelse:
                stmt(b)
        elif isinstance(s, ast.If):
            # branch markers let flow-sensitive rules (donation) fork
            # their state: the else-branch never observes the
            # if-branch's effects
            expr(s.test)
            events.append(("branch", "start", s.lineno))
            for b in s.body:
                stmt(b)
            events.append(("branch", "alt", s.lineno))
            for b in s.orelse:
                stmt(b)
            events.append(("branch", "end", s.lineno))
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                expr(item.context_expr)
                if item.optional_vars is not None:
                    assign_target(item.optional_vars)
            for b in s.body:
                stmt(b)
        elif isinstance(s, ast.Try):
            for b in s.body:
                stmt(b)
            for h in s.handlers:
                expr(h.type)
                for b in h.body:
                    stmt(b)
            for b in s.orelse + s.finalbody:
                stmt(b)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested def: FREE-variable loads count at the def site (a
            # closure reading a donated buffer later is still a
            # hazard); names the nested def binds itself — args,
            # stores, inner defs — are its own locals, not reads of
            # the enclosing scope
            a = s.args
            local = {x.arg for x in (a.posonlyargs + a.args +
                                     a.kwonlyargs +
                                     ([a.vararg] if a.vararg else []) +
                                     ([a.kwarg] if a.kwarg else []))}
            for inner in ast.walk(s):
                if isinstance(inner, ast.Name) and isinstance(
                        inner.ctx, ast.Store):
                    local.add(inner.id)
                elif isinstance(inner, (ast.FunctionDef,
                                        ast.AsyncFunctionDef,
                                        ast.ClassDef)) and inner is not s:
                    local.add(inner.name)
            for inner in ast.walk(s):
                if isinstance(inner, ast.Name) and isinstance(
                        inner.ctx, ast.Load) and inner.id not in local:
                    events.append(("load", inner.id, inner.lineno))
        elif isinstance(s, ast.ClassDef):
            pass
        elif isinstance(s, (ast.Return, ast.Expr, ast.Raise, ast.Assert,
                            ast.Delete)):
            for child in ast.iter_child_nodes(s):
                expr(child)
        else:
            for child in ast.iter_child_nodes(s):
                expr(child)

    for s in body:
        stmt(s)
    return events


def _functions(module: Module):
    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


# ---------------------------------------------------------------------------
# project: cross-module registries collected in pass 1
# ---------------------------------------------------------------------------

@dataclass
class OwnerClass:
    name: str
    module: str                 # dotted defining module
    mutators: Tuple[str, ...]
    owners: Tuple[str, ...]     # substrings of allowed dotted modules


@dataclass
class Project:
    root: str
    modules: List[Module] = field(default_factory=list)
    replay_fields: Tuple[str, ...] = DEFAULT_REPLAY_FIELDS
    owner_classes: Dict[str, OwnerClass] = field(default_factory=dict)
    fn_owners: Dict[str, Tuple[str, Tuple[str, ...]]] = \
        field(default_factory=dict)     # fn name -> (module, owners)
    factories: Dict[str, str] = field(default_factory=dict)
    knobs: List[Tuple[str, str, str]] = field(default_factory=list)
    knobs_at: Tuple[str, int] = ("", 0)  # (path, line) of KNOBS literal
    doc_text: Dict[str, str] = field(default_factory=dict)

    def collect(self) -> None:
        for m in self.modules:
            for node in m.tree.body:
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    name = node.targets[0].id
                    if name == "REPLAY_FIELDS":
                        val = _literal(node.value)
                        if isinstance(val, tuple):
                            self.replay_fields = val
                    elif name == "KNOBS":
                        knobs = _knob_literal(node.value)
                        if knobs is not None:
                            self.knobs = knobs
                            self.knobs_at = (m.path, node.lineno)
                    elif name == "__apex_fn_owners__":
                        for fn, owners in _dict_literal(node.value).items():
                            self.fn_owners[fn] = (m.dotted, owners)
                    elif name == "__apex_factories__":
                        for fac, cls in _dict_literal(node.value).items():
                            if isinstance(cls, str):
                                self.factories[fac] = cls
                            elif isinstance(cls, tuple) and cls:
                                self.factories[fac] = cls[0]
                elif isinstance(node, ast.ClassDef):
                    muts = owners = None
                    for st in node.body:
                        if isinstance(st, ast.Assign) and \
                                len(st.targets) == 1 and \
                                isinstance(st.targets[0], ast.Name):
                            v = _literal(st.value)
                            if st.targets[0].id == "__apex_mutators__" \
                                    and isinstance(v, tuple):
                                muts = v
                            elif st.targets[0].id == "__apex_owner__" \
                                    and isinstance(v, tuple):
                                owners = v
                    if muts:
                        self.owner_classes[node.name] = OwnerClass(
                            node.name, m.dotted, muts, owners or ())
        for doc in ("README.md", "TESTING.md"):
            p = os.path.join(self.root, doc)
            try:
                with open(p) as f:
                    self.doc_text[doc] = f.read()
            except OSError:
                self.doc_text[doc] = ""


def _dict_literal(node: ast.AST) -> Dict[str, Tuple[str, ...]]:
    out: Dict[str, Tuple[str, ...]] = {}
    if isinstance(node, ast.Dict):
        for k, v in zip(node.keys, node.values):
            kk, vv = _literal(k) if k is not None else None, _literal(v)
            if isinstance(kk, str) and vv is not None:
                out[kk] = vv if isinstance(vv, tuple) else (vv,)
    return out


def _knob_literal(node: ast.AST) -> Optional[List[Tuple[str, str, str]]]:
    """Parse ``KNOBS = ((name, where, doc), ...)`` without importing."""
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    rows: List[Tuple[str, str, str]] = []
    for e in node.elts:
        row = _literal(e)
        if not (isinstance(row, tuple) and len(row) == 3):
            return None
        rows.append(row)  # type: ignore[arg-type]
    return rows


# ---------------------------------------------------------------------------
# shared: jit registries (donating + static positions) per module
# ---------------------------------------------------------------------------

def _donate_positions(call: ast.Call) -> Set[int]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            return _int_set(kw.value)
    return set()


def _static_positions(call: ast.Call) -> Set[int]:
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            return _int_set(kw.value)
    return set()


def _int_set(node: ast.AST) -> Set[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[int] = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.add(e.value)
        return out
    if isinstance(node, ast.IfExp):  # (0,) if donate else () — union
        return _int_set(node.body) | _int_set(node.orelse)
    return set()


def _target_key(t: ast.AST) -> Optional[str]:
    """'name' or 'self.attr' binding key for jit/instance registries."""
    if isinstance(t, ast.Name):
        return t.id
    if isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name) \
            and t.value.id == "self":
        return f"self.{t.attr}"
    return None


def _callee_key(node: ast.Call) -> Optional[str]:
    return _target_key(node.func)


def _jit_registry(module: Module) -> Tuple[Dict[str, Set[int]],
                                           Dict[str, Set[int]]]:
    """Maps of var/'self.attr' -> donated / static positions, for every
    ``x = jax.jit(...)`` binding in the module."""
    donating: Dict[str, Set[int]] = {}
    static: Dict[str, Set[int]] = {}
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Assign) or \
                not isinstance(node.value, ast.Call):
            continue
        callee = module.resolve(node.value.func)
        if not callee.endswith("jax.jit") and callee != "jit":
            continue
        for t in node.targets:
            key = _target_key(t)
            if key is None:
                continue
            d = _donate_positions(node.value)
            if d:
                donating[key] = d
            static.setdefault(key, _static_positions(node.value))
    return donating, static


# ---------------------------------------------------------------------------
# rule: donation-after-use
# ---------------------------------------------------------------------------

def check_donation(module: Module) -> List[Finding]:
    donating, _ = _jit_registry(module)
    if not donating:
        return []
    out: List[Finding] = []
    for fn in _functions(module):
        pending: Dict[str, Tuple[int, str]] = {}  # name -> (line, callee)
        flagged: Set[Tuple[str, int]] = set()
        # if/else fork stack: (snapshot-at-test, if-branch result)
        branches: List[Tuple[dict, Optional[dict]]] = []
        for kind, payload, line in iter_events(fn.body):
            if kind == "branch":
                if payload == "start":
                    branches.append((dict(pending), None))
                elif payload == "alt" and branches:
                    snap, _ = branches[-1]
                    branches[-1] = (snap, dict(pending))
                    pending.clear()
                    pending.update(snap)
                elif payload == "end" and branches:
                    _snap, body_result = branches.pop()
                    if body_result:
                        # after the if: either branch may have donated
                        pending.update(body_result)
            elif kind == "call":
                key = _callee_key(payload)
                if key in donating:
                    for pos in donating[key]:
                        if pos < len(payload.args) and isinstance(
                                payload.args[pos], ast.Name):
                            pending[payload.args[pos].id] = (line, key)
            elif kind == "store":
                pending.pop(payload, None)
            elif kind == "load" and payload in pending:
                dline, callee = pending[payload]
                if (payload, line) in flagged or line == dline:
                    continue
                flagged.add((payload, line))
                out.append(Finding(
                    module.path, line, "donation-after-use",
                    f"'{payload}' is read after being donated to "
                    f"'{callee}'",
                    f"rebind the variable from the dispatch's result "
                    f"(donation at line {dline}), or drop donate_argnums "
                    f"for this argument",
                    _context_of(module, fn)))
    return out


# ---------------------------------------------------------------------------
# rule: rng-key-reuse
# ---------------------------------------------------------------------------

def _is_key_derivation(callee: str) -> bool:
    last = callee.rsplit(".", 1)[-1]
    return last in _KEY_PURE


def _is_key_consumer(callee: str) -> bool:
    if callee.rsplit(".", 1)[-1] == "split":
        return True  # split invalidates its operand: use the outputs
    return "jax.random." in callee and not _is_key_derivation(callee)


def check_rng(module: Module) -> List[Finding]:
    out: List[Finding] = []
    allow_literal = module.dotted.endswith("utils.rngs")
    for fn in _functions(module):
        key_vars: Set[str] = set()
        for a in (fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs):
            if _KEY_PARAM.search(a.arg):
                key_vars.add(a.arg)
        # vars bound from a derivation call are keys whatever their name
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call):
                last = module.resolve(
                    node.value.func).rsplit(".", 1)[-1]
                if last in ("split", "fold_in", "PRNGKey", "tick_keys",
                            "process_key"):
                    for t in node.targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name) and isinstance(
                                    n.ctx, ast.Store):
                                key_vars.add(n.id)
        consumed: Dict[str, Tuple[int, str]] = {}
        # if/else fork stack, mirroring check_donation: a consumption
        # in the if-branch is never visible to the else-branch
        branches: List[Tuple[dict, Optional[dict]]] = []
        for kind, payload, line in iter_events(fn.body):
            if kind == "branch":
                if payload == "start":
                    branches.append((dict(consumed), None))
                elif payload == "alt" and branches:
                    snap, _ = branches[-1]
                    branches[-1] = (snap, dict(consumed))
                    consumed.clear()
                    consumed.update(snap)
                elif payload == "end" and branches:
                    _snap, body_result = branches.pop()
                    if body_result:
                        consumed.update(body_result)
                continue
            if kind == "store":
                consumed.pop(payload, None)
                continue
            if kind != "call":
                continue
            callee = module.resolve(payload.func)
            # literal-seed PRNGKey: streams must fold from the run seed
            if callee.rsplit(".", 1)[-1] == "PRNGKey" and payload.args \
                    and isinstance(payload.args[0], ast.Constant) \
                    and not allow_literal \
                    and not module.suppressed(line, "rng-key-reuse"):
                out.append(Finding(
                    module.path, line, "rng-key-reuse",
                    f"PRNGKey({payload.args[0].value!r}) minted from a "
                    f"literal seed — a fixed stream colliding with every "
                    f"other literal-seed stream",
                    "derive the key from the run seed "
                    "(utils.rngs.process_key / fold_in of an existing "
                    "stream)",
                    _context_of(module, fn)))
            # track derived keys as they are bound elsewhere (store
            # events already clear consumption)
            if not _is_key_consumer(callee):
                continue
            for arg in list(payload.args) + \
                    [kw.value for kw in payload.keywords]:
                if not isinstance(arg, ast.Name) or \
                        arg.id not in key_vars and \
                        not _KEY_PARAM.search(arg.id):
                    continue
                name = arg.id
                if name in consumed:
                    first_line, first_callee = consumed[name]
                    if line != first_line and not module.suppressed(
                            line, "rng-key-reuse"):
                        out.append(Finding(
                            module.path, line, "rng-key-reuse",
                            f"PRNG key '{name}' consumed by "
                            f"'{callee}' after already being consumed "
                            f"by '{first_callee}' with no rebind "
                            f"between",
                            f"split/fold_in a fresh key per consumer "
                            f"(first consumption at line {first_line}; "
                            f"tick_keys stream contract)",
                            _context_of(module, fn)))
                        consumed.pop(name, None)
                else:
                    consumed[name] = (line, callee)
    return out


# ---------------------------------------------------------------------------
# rule: retrace-hazard
# ---------------------------------------------------------------------------

def check_retrace(module: Module) -> List[Finding]:
    donating, static = _jit_registry(module)
    jitted = set(donating) | set(static)
    out: List[Finding] = []
    for fn in _functions(module):
        # python scalar counters: assigned from an int/float literal
        scalar_consts: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Constant) and isinstance(
                    node.value.value, (int, float)):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        scalar_consts.add(t.id)
        for loop in ast.walk(fn):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            induction: Set[str] = set()
            if isinstance(loop, ast.For):
                it = loop.iter
                callee = module.resolve(it.func) if isinstance(
                    it, ast.Call) else ""
                if callee in ("range", "enumerate"):
                    tgt = loop.target
                    if isinstance(tgt, ast.Name):
                        induction.add(tgt.id)
                    elif isinstance(tgt, ast.Tuple) and callee == \
                            "enumerate" and tgt.elts and isinstance(
                            tgt.elts[0], ast.Name):
                        induction.add(tgt.elts[0].id)
            bumped: Set[str] = set()
            for node in ast.walk(loop):
                if isinstance(node, ast.AugAssign) and isinstance(
                        node.target, ast.Name) and \
                        node.target.id in scalar_consts:
                    bumped.add(node.target.id)
                elif isinstance(node, ast.Assign) and isinstance(
                        node.value, ast.BinOp):
                    for t in node.targets:
                        if isinstance(t, ast.Name) and \
                                t.id in scalar_consts and any(
                                isinstance(n, ast.Name) and n.id == t.id
                                for n in ast.walk(node.value)):
                            bumped.add(t.id)
            hazards = induction | bumped
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                key = _callee_key(node)
                if key not in jitted:
                    continue
                for i, arg in enumerate(node.args):
                    if isinstance(arg, ast.Name) and arg.id in hazards \
                            and not module.suppressed(node.lineno,
                                                      "retrace-hazard"):
                        out.append(Finding(
                            module.path, node.lineno, "retrace-hazard",
                            f"python scalar '{arg.id}' varies per "
                            f"iteration and flows into jitted "
                            f"'{key}' — every call retraces",
                            "keep the counter device-resident "
                            "(jnp.int32 carry advanced on device) or "
                            "fold it into the traced key stream",
                            _context_of(module, fn)))
                    if isinstance(arg, (ast.List, ast.Dict, ast.Set)) \
                            and i in static.get(key, set()) \
                            and not module.suppressed(node.lineno,
                                                      "retrace-hazard"):
                        out.append(Finding(
                            module.path, node.lineno, "retrace-hazard",
                            f"unhashable {type(arg).__name__.lower()} "
                            f"literal at static_argnums position {i} of "
                            f"jitted '{key}'",
                            "static args must be hashable — pass a "
                            "tuple or hoist to a closure constant",
                            _context_of(module, fn)))
    return out


# ---------------------------------------------------------------------------
# rule: single-owner
# ---------------------------------------------------------------------------

def _owned(dotted_module: str, defining: str,
           owners: Tuple[str, ...]) -> bool:
    if dotted_module == defining:
        return True
    return any(o in dotted_module for o in owners)


def check_single_owner(module: Module, project: Project) -> List[Finding]:
    if not project.owner_classes and not project.fn_owners:
        return []
    out: List[Finding] = []
    # provenance: var/'self.attr' -> owning class name
    instances: Dict[str, str] = {}
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call):
            cls = module.resolve(node.value.func).rsplit(".", 1)[-1]
            if cls in project.owner_classes:
                for t in node.targets:
                    key = _target_key(t)
                    if key:
                        instances[key] = cls

    def class_of_receiver(recv: ast.AST) -> Optional[str]:
        key = _target_key(recv)
        if key and key in instances:
            return instances[key]
        if isinstance(recv, ast.Call):  # factory(...).mutator(...)
            fac = module.resolve(recv.func).rsplit(".", 1)[-1]
            return project.factories.get(fac)
        return None

    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute):
            cls = class_of_receiver(f.value)
            oc = project.owner_classes.get(cls) if cls else None
            if oc and f.attr in oc.mutators and not _owned(
                    module.dotted, oc.module, oc.owners) and \
                    not module.suppressed(node.lineno, "single-owner"):
                out.append(Finding(
                    module.path, node.lineno, "single-owner",
                    f"{cls}.{f.attr}() invoked outside its declared "
                    f"owner set {oc.owners}",
                    "route the mutation through the owning role (or "
                    "extend __apex_owner__ if this module truly owns "
                    "the boundary)",
                    _context_of(module, node)))
        else:
            fname = module.resolve(f).rsplit(".", 1)[-1]
            if fname in project.fn_owners:
                defining, owners = project.fn_owners[fname]
                if not _owned(module.dotted, defining, owners) and \
                        not module.suppressed(node.lineno,
                                              "single-owner"):
                    out.append(Finding(
                        module.path, node.lineno, "single-owner",
                        f"{fname}() invoked outside its declared owner "
                        f"set {owners}",
                        "single-owner ring mutations belong to the "
                        "replay/rollout planes — route through them",
                        _context_of(module, node)))
    return out


# ---------------------------------------------------------------------------
# rule: schema-contract
# ---------------------------------------------------------------------------

_SCHEMA_CLASSES = ("Transition", "Segment")


def check_schema(module: Module, project: Project) -> List[Finding]:
    out: List[Finding] = []
    is_schema_home = module.dotted.endswith("utils.experience")
    rf = project.replay_fields

    # (a) positional subscript on provable Transition/Segment values
    rows: Dict[str, str] = {}
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call):
            cls = module.resolve(node.value.func).rsplit(".", 1)[-1]
            if cls in _SCHEMA_CLASSES:
                for t in node.targets:
                    key = _target_key(t)
                    if key:
                        rows[key] = cls
    for fn in _functions(module):
        for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs:
            ann = a.annotation
            if ann is not None:
                nm = module.resolve(ann).rsplit(".", 1)[-1] if isinstance(
                    ann, (ast.Name, ast.Attribute)) else ""
                if nm in _SCHEMA_CLASSES:
                    rows[a.arg] = nm
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Subscript) and isinstance(
                node.value, ast.Name) and node.value.id in rows:
            idx = node.slice
            if isinstance(idx, ast.Constant) and isinstance(
                    idx.value, int) and not module.suppressed(
                    node.lineno, "schema-contract"):
                cls = rows[node.value.id]
                fname = (rf[idx.value] if cls == "Transition"
                         and 0 <= idx.value < len(rf)
                         else f"field {idx.value}")
                out.append(Finding(
                    module.path, node.lineno, "schema-contract",
                    f"positional index [{idx.value}] into a {cls} row",
                    f"use the named field (.{fname}) — positional "
                    f"offsets break silently when the schema grows",
                    _context_of(module, node)))

    # (b) ._fields where the replay schema is meant
    if not is_schema_home:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr == "_fields" and isinstance(
                    node.value, (ast.Name, ast.Attribute)):
                cls = module.resolve(node.value).rsplit(".", 1)[-1]
                if cls in _SCHEMA_CLASSES and not module.suppressed(
                        node.lineno, "schema-contract"):
                    out.append(Finding(
                        module.path, node.lineno, "schema-contract",
                        f"{cls}._fields used for the replay schema — "
                        f"it now also carries the provenance sidecar",
                        "iterate REPLAY_FIELDS (utils.experience) when "
                        "you mean the six replay columns",
                        _context_of(module, node)))

    # (c) shadow replay-schema tuples (re-typed copies drift silently)
    if not is_schema_home:
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.Tuple, ast.List)):
                val = _literal(node)
                if isinstance(val, tuple) and len(val) >= 4 and \
                        val == rf[: len(val)] and not module.suppressed(
                        node.lineno, "schema-contract"):
                    out.append(Finding(
                        module.path, node.lineno, "schema-contract",
                        "re-typed copy of the replay schema tuple "
                        f"{val[:3] + ('...',)}",
                        "import REPLAY_FIELDS from utils.experience — "
                        "a shadow schema drifts silently when a column "
                        "is added",
                        _context_of(module, node)))

    # (d) wire columns must stay inside the declared WIRE_COLUMNS
    wire = module.constants.get("WIRE_COLUMNS")
    if wire is None:
        # WIRE_COLUMNS may be REPLAY_FIELDS + (...,): resolve the concat
        for node in module.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) and \
                    node.targets[0].id == "WIRE_COLUMNS" and \
                    isinstance(node.value, ast.BinOp) and isinstance(
                    node.value.op, ast.Add):
                left = module.resolve(node.value.left).rsplit(".", 1)[-1]
                right = _literal(node.value.right)
                if left in ("REPLAY_FIELDS", "_FIELDS") and isinstance(
                        right, tuple):
                    wire = rf + right
    if wire:
        allowed = set(wire) | set(rf)
        for fn in _functions(module):
            if not (fn.name.startswith("encode")
                    or fn.name.startswith("decode")):
                continue
            for node in ast.walk(fn):
                key = None
                if isinstance(node, ast.Subscript) and isinstance(
                        node.slice, ast.Constant) and isinstance(
                        node.slice.value, str):
                    key = node.slice.value
                elif isinstance(node, ast.Call) and isinstance(
                        node.func, ast.Attribute) and \
                        node.func.attr == "get" and node.args and \
                        isinstance(node.args[0], ast.Constant) and \
                        isinstance(node.args[0].value, str):
                    key = node.args[0].value
                if key is not None and key not in allowed and \
                        not module.suppressed(node.lineno,
                                              "schema-contract"):
                    out.append(Finding(
                        module.path, node.lineno, "schema-contract",
                        f"wire column '{key}' is not in the declared "
                        f"WIRE_COLUMNS schema",
                        "add it to WIRE_COLUMNS (and bump peers) or "
                        "drop the stray column",
                        _context_of(module, fn)))
    return out


# ---------------------------------------------------------------------------
# rule: knob-registry
# ---------------------------------------------------------------------------

def _string_patterns(node: ast.AST, module: Module,
                     fn: Optional[ast.AST],
                     depth: int = 0) -> Optional[List[str]]:
    """Glob patterns an expression may evaluate to, or None if opaque."""
    if depth > 6:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.IfExp):
        a = _string_patterns(node.body, module, fn, depth + 1)
        b = _string_patterns(node.orelse, module, fn, depth + 1)
        if a is None or b is None:
            return None
        return a + b
    if isinstance(node, ast.JoinedStr):
        parts: List[str] = []
        for v in node.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            else:
                parts.append("*")
        pat = "".join(parts)
        return [pat] if pat.strip("*") else None
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _string_patterns(node.left, module, fn, depth + 1)
        right = _string_patterns(node.right, module, fn, depth + 1)
        if left is None:
            return None
        rights = right if right is not None else ["*"]
        return [a + b for a in left for b in rights]
    if isinstance(node, ast.Name):
        if node.id in module.constants and isinstance(
                module.constants[node.id], str):
            return [module.constants[node.id]]
        pats: List[str] = []
        if fn is not None:
            for st in ast.walk(fn):
                if isinstance(st, ast.Assign):
                    for t in st.targets:
                        if isinstance(t, ast.Name) and t.id == node.id:
                            p = _string_patterns(st.value, module, fn,
                                                 depth + 1)
                            if p:
                                pats.extend(p)
        return pats or None
    if isinstance(node, ast.Call):
        return ["*"]  # role.upper() etc. — a wildcard segment
    return None


def _covers(read_pat: str, knob_name: str) -> bool:
    if read_pat == knob_name:
        return True
    # a concrete read against a family declaration (or vice versa);
    # identical families compare equal above
    return fnmatch.fnmatchcase(read_pat, knob_name) or \
        fnmatch.fnmatchcase(knob_name, read_pat)


def _enclosing_function(module: Module, target: ast.AST
                        ) -> Optional[ast.AST]:
    best: Optional[ast.AST] = None

    def walk(node: ast.AST, cur: Optional[ast.AST]) -> bool:
        nonlocal best
        if node is target:
            best = cur
            return True
        for child in ast.iter_child_nodes(node):
            nxt = child if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)) else cur
            if walk(child, nxt):
                return True
        return False

    walk(module.tree, None)
    return best


def _env_read_sites(module: Module) -> List[Tuple[ast.AST, ast.AST]]:
    """(arg-expression, site-node) for every env READ in the module."""
    sites: List[Tuple[ast.AST, ast.AST]] = []
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Call):
            callee = module.resolve(node.func)
            if callee.endswith("os.environ.get") or \
                    callee.endswith("os.getenv") or callee == "getenv":
                if node.args:
                    sites.append((node.args[0], node))
        elif isinstance(node, ast.Subscript) and isinstance(
                node.ctx, ast.Load):
            if module.resolve(node.value).endswith("os.environ"):
                sites.append((node.slice, node))
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 and \
                isinstance(node.ops[0], (ast.In, ast.NotIn)):
            if module.resolve(node.comparators[0]).endswith("os.environ"):
                sites.append((node.left, node))
    return sites


def _resolve_read(module: Module, arg: ast.AST, site: ast.AST,
                  ) -> Optional[List[str]]:
    """Patterns for one env-read argument; follows one level of
    call-site propagation when the arg is a parameter of the enclosing
    helper (``_env_flag(name, ...)`` style)."""
    fn = _enclosing_function(module, site)
    pats = _string_patterns(arg, module, fn)
    if pats:
        return pats
    if isinstance(arg, ast.Name) and fn is not None and isinstance(
            fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        params = [a.arg for a in fn.args.args]
        if arg.id in params:
            pos = params.index(arg.id)
            collected: List[str] = []
            for node in ast.walk(module.tree):
                if isinstance(node, ast.Call) and \
                        module.resolve(node.func).rsplit(
                            ".", 1)[-1] == fn.name and node is not site:
                    cand: Optional[ast.AST] = None
                    if pos < len(node.args):
                        cand = node.args[pos]
                    for kw in node.keywords:
                        if kw.arg == arg.id:
                            cand = kw.value
                    if cand is not None:
                        p = _string_patterns(
                            cand, module,
                            _enclosing_function(module, node))
                        if p:
                            collected.extend(p)
            if collected:
                return collected
    return None


def check_knobs(module: Module, project: Project,
                read_patterns: List[str]) -> List[Finding]:
    """Code-side half: every in-scope env read must be declared.
    ``read_patterns`` accumulates resolved patterns for the reverse
    (registry-side) half run once per project."""
    out: List[Finding] = []
    declared = [k[0] for k in project.knobs]
    for arg, site in _env_read_sites(module):
        pats = _resolve_read(module, arg, site)
        if pats is None:
            # opaque dynamic read: only a finding when the expression
            # carries an in-scope fragment (f"TPU_APEX_{x}" etc.)
            frag = ast.dump(arg)
            if ("TPU_APEX" in frag or "_FAULTS" in frag) and \
                    not module.suppressed(site.lineno, "knob-registry"):
                out.append(Finding(
                    module.path, site.lineno, "knob-registry",
                    "dynamic env knob read is not statically resolvable",
                    "build the name from a declared prefix constant so "
                    "the registry rule can see it",
                    _context_of(module, site)))
            continue
        for pat in pats:
            if pat.strip("*"):
                # pure-wildcard patterns (opaque call args) carry no
                # name information: appending them would fnmatch every
                # declared knob and silently disable the declared-but-
                # never-read check
                read_patterns.append(pat)
            if not KNOB_SCOPE.search(pat.replace("*", "X")) and \
                    not KNOB_SCOPE.search(pat):
                continue
            if not any(_covers(pat, name) for name in declared) and \
                    not module.suppressed(site.lineno, "knob-registry"):
                out.append(Finding(
                    module.path, site.lineno, "knob-registry",
                    f"env knob '{pat}' read here is not declared in "
                    f"config.KNOBS",
                    "add a (name, where, doc) row to config.KNOBS and "
                    "document it in README.md + TESTING.md",
                    _context_of(module, site)))
    return out


def check_knob_registry_side(project: Project,
                             read_patterns: List[str]) -> List[Finding]:
    out: List[Finding] = []
    path, line = project.knobs_at
    if not project.knobs:
        if any(KNOB_SCOPE.search(p.replace("*", "X"))
               for p in read_patterns):
            out.append(Finding(
                path or "config.py", line or 1, "knob-registry",
                "no KNOBS declaration table found but TPU_APEX_*/"
                "*_FAULTS knobs are read in code",
                "declare the table: KNOBS = ((name, where, doc), ...)",
                "KNOBS"))
        return out
    for name, _where, _doc in project.knobs:
        if not any(_covers(p, name) or _covers(name, p)
                   for p in read_patterns):
            out.append(Finding(
                path, line, "knob-registry",
                f"knob '{name}' is declared in config.KNOBS but never "
                f"read in the scanned code",
                "delete the dead declaration (and its doc rows) or "
                "wire the knob up",
                "KNOBS"))
        token = name.rstrip("*").rstrip("_") if name != "*_FAULTS" \
            else "_FAULTS"
        for doc in ("README.md", "TESTING.md"):
            if token and token not in project.doc_text.get(doc, ""):
                out.append(Finding(
                    path, line, "knob-registry",
                    f"knob '{name}' is declared but undocumented in "
                    f"{doc}",
                    f"add it to the knob table in {doc}",
                    "KNOBS"))
    return out


# ---------------------------------------------------------------------------
# generic pass: scopes
# ---------------------------------------------------------------------------

_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef,
                ast.Lambda, ast.ListComp, ast.SetComp, ast.DictComp,
                ast.GeneratorExp)


class _Scope:
    __slots__ = ("node", "parent", "kind", "bindings", "used",
                 "has_star", "globals")

    def __init__(self, node, parent, kind):
        self.node = node
        self.parent = parent
        self.kind = kind  # module | class | function
        self.bindings: Dict[str, Tuple[int, str]] = {}
        self.used: Set[str] = set()
        self.has_star = False
        self.globals: Set[str] = set()


def _bind(scope: _Scope, name: str, line: int, kind: str) -> None:
    scope.bindings.setdefault(name, (line, kind))


def _build_scopes(module: Module, parents: Dict[ast.AST, ast.AST]
                  ) -> Tuple[_Scope, Dict[ast.AST, _Scope]]:
    """Scope tree with AST-true parent chains (so nested
    comprehensions/lambdas resolve through every enclosing scope)."""
    module_scope = _Scope(module.tree, None, "module")
    by_node: Dict[ast.AST, _Scope] = {module.tree: module_scope}

    def scope_of(node: ast.AST) -> _Scope:
        cur: Optional[ast.AST] = node
        while cur is not None:
            if cur in by_node:
                return by_node[cur]
            cur = parents.get(cur)
        return module_scope

    # create scopes top-down (ast.walk is BFS: parents come first)
    for node in ast.walk(module.tree):
        if isinstance(node, _SCOPE_NODES):
            parent = scope_of(parents.get(node, module.tree))
            kind = "class" if isinstance(node, ast.ClassDef) \
                else "function"
            by_node[node] = _Scope(node, parent, kind)

    # collect bindings into their owning scope
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            _bind(scope_of(node), node.id, node.lineno, "assign")
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            # the def's NAME binds in the enclosing scope; its args in
            # its own
            _bind(by_node[node].parent, node.name, node.lineno, "def")
            if not isinstance(node, ast.ClassDef):
                a = node.args
                for arg in (a.posonlyargs + a.args + a.kwonlyargs +
                            ([a.vararg] if a.vararg else []) +
                            ([a.kwarg] if a.kwarg else [])):
                    _bind(by_node[node], arg.arg, arg.lineno, "arg")
        elif isinstance(node, ast.Lambda):
            a = node.args
            for arg in (a.posonlyargs + a.args + a.kwonlyargs +
                        ([a.vararg] if a.vararg else []) +
                        ([a.kwarg] if a.kwarg else [])):
                _bind(by_node[node], arg.arg, node.lineno, "arg")
        elif isinstance(node, ast.Import):
            s = scope_of(node)
            for a in node.names:
                local = a.asname or a.name.split(".")[0]
                _bind(s, local, node.lineno,
                      "import-reexport" if a.asname == a.name
                      else "import")
        elif isinstance(node, ast.ImportFrom):
            s = scope_of(node)
            for a in node.names:
                if a.name == "*":
                    s.has_star = True
                    continue
                kind = "import"
                if node.module == "__future__":
                    kind = "import-future"
                elif a.asname == a.name:
                    kind = "import-reexport"
                _bind(s, a.asname or a.name, node.lineno, kind)
        elif isinstance(node, ast.ExceptHandler) and node.name:
            _bind(scope_of(node), node.name, node.lineno, "except")
        elif isinstance(node, ast.Global):
            s = scope_of(node)
            s.globals.update(node.names)
            for n in node.names:
                _bind(s, n, node.lineno, "global")
                _bind(module_scope, n, node.lineno, "global")
        elif isinstance(node, ast.Nonlocal):
            for n in node.names:
                _bind(scope_of(node), n, node.lineno, "nonlocal")
        elif isinstance(node, (ast.MatchAs, ast.MatchStar)) and \
                getattr(node, "name", None):
            _bind(scope_of(node), node.name, node.lineno, "assign")
    return module_scope, by_node


def check_generic(module: Module) -> List[Finding]:
    out: List[Finding] = []
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(module.tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    module_scope, by_node = _build_scopes(module, parents)
    scopes = list(by_node.values())

    def scope_of(node: ast.AST) -> _Scope:
        cur: Optional[ast.AST] = node
        while cur is not None:
            if cur in by_node:
                return by_node[cur]
            cur = parents.get(cur)
        return module_scope

    # annotation subtrees: loads there count as usage, never undefined
    ann_nodes: Set[ast.AST] = set()
    for node in ast.walk(module.tree):
        anns: List[Optional[ast.AST]] = []
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            anns.append(node.returns)
            args = node.args
            for a in (args.posonlyargs + args.args + args.kwonlyargs +
                      ([args.vararg] if args.vararg else []) +
                      ([args.kwarg] if args.kwarg else [])):
                anns.append(a.annotation)
        elif isinstance(node, ast.AnnAssign):
            anns.append(node.annotation)
        for ann in anns:
            if ann is not None:
                for n in ast.walk(ann):
                    ann_nodes.add(n)

    star_anywhere = any(s.has_star for s in scopes)
    all_names: Set[str] = set()
    for node in module.tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    v = _literal(node.value)
                    if isinstance(v, tuple):
                        all_names.update(v)

    # pass 2: resolve loads
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Name) or not isinstance(
                node.ctx, ast.Load):
            continue
        name = node.id
        s: Optional[_Scope] = scope_of(node)
        found = False
        first = True
        while s is not None:
            if (s.kind != "class" or first) and name in s.bindings:
                s.used.add(name)
                found = True
                break
            first = False
            s = s.parent
        if not found and name not in _BUILTIN_NAMES and \
                not star_anywhere and node not in ann_nodes and \
                not module.suppressed(node.lineno, "undefined-name"):
            out.append(Finding(
                module.path, node.lineno, "undefined-name",
                f"name '{name}' is not defined in any enclosing scope",
                "define/import it (or gate the branch that uses it)",
                _context_of(module, node)))

    # docstring/doctest references don't count; __all__ does
    for name in all_names:
        if name in module_scope.bindings:
            module_scope.used.add(name)

    # unused imports (module API files re-export by design)
    if not module.is_init:
        for s in scopes:
            for name, (line, kind) in s.bindings.items():
                if kind != "import" or name in s.used:
                    continue
                if name == "_" or name.startswith("__"):
                    continue
                if module.suppressed(line, "unused-import"):
                    continue
                out.append(Finding(
                    module.path, line, "unused-import",
                    f"'{name}' is imported but never used",
                    "drop the import",
                    ""))

    # shadowed builtins (function/module scopes; class attrs are fine)
    for s in scopes:
        if s.kind == "class":
            continue
        for name, (line, kind) in s.bindings.items():
            if name in _SHADOW_BUILTINS and kind in (
                    "assign", "arg", "for", "def", "with", "except"):
                if not module.suppressed(line, "shadowed-builtin"):
                    out.append(Finding(
                        module.path, line, "shadowed-builtin",
                        f"'{name}' shadows the builtin of the same "
                        f"name",
                        "rename the binding",
                        ""))
    return out


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

@dataclass
class Report:
    findings: List[Finding] = field(default_factory=list)
    suppressed: int = 0
    stale: List[dict] = field(default_factory=list)
    files: int = 0
    # baseline entries that matched a finding this run (justifications
    # preserved by --write-baseline), and entries outside this run's
    # rule/path scope (carried, neither matched nor stale: a subset
    # invocation must not strand or destroy them)
    matched_entries: List[dict] = field(default_factory=list)
    carried_entries: List[dict] = field(default_factory=list)

    @property
    def clean(self) -> bool:
        return not self.findings and not self.stale

    def to_json(self) -> dict:
        counts: Dict[str, int] = {}
        for f in self.findings:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        return {
            "files": self.files,
            "findings": [f.to_json() for f in self.findings],
            "baselined": self.suppressed,
            "stale_baseline": self.stale,
            "counts": counts,
            "clean": self.clean,
        }


def _iter_py_files(paths: List[str], root: str) -> List[Tuple[str, str]]:
    out: List[Tuple[str, str]] = []
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(ap):
            out.append((ap, os.path.relpath(ap, root)))
            continue
        for dirpath, dirnames, filenames in os.walk(ap):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    fp = os.path.join(dirpath, fn)
                    out.append((fp, os.path.relpath(fp, root)))
    return out


def run(paths: List[str], root: Optional[str] = None,
        baseline: Optional[str] = None,
        rules: Optional[Set[str]] = None) -> Report:
    root = os.path.abspath(root or os.getcwd())
    report = Report()
    project = Project(root=root)
    for abspath, relpath in _iter_py_files(paths, root):
        try:
            with open(abspath) as f:
                text = f.read()
            project.modules.append(Module(abspath, relpath, text))
        except SyntaxError as e:
            report.findings.append(Finding(
                relpath.replace(os.sep, "/"), e.lineno or 1,
                "parse-error", f"syntax error: {e.msg}",
                "fix the syntax", ""))
        except ValueError as e:
            # e.g. NUL bytes: ast.parse raises ValueError, not
            # SyntaxError — still a per-file finding, never a crash
            report.findings.append(Finding(
                relpath.replace(os.sep, "/"), 1, "parse-error",
                f"unparseable source: {e}", "fix the file", ""))
        except OSError as e:
            report.findings.append(Finding(
                relpath.replace(os.sep, "/"), 1, "parse-error",
                f"unreadable: {e}", "fix the file", ""))
    report.files = len(project.modules)
    project.collect()

    def want(rule: str) -> bool:
        return rules is None or rule in rules

    read_patterns: List[str] = []
    for m in project.modules:
        if want("donation-after-use"):
            report.findings.extend(
                f for f in check_donation(m)
                if not m.suppressed(f.line, f.rule))
        if want("rng-key-reuse"):
            report.findings.extend(check_rng(m))
        if want("retrace-hazard"):
            report.findings.extend(check_retrace(m))
        if want("single-owner"):
            report.findings.extend(check_single_owner(m, project))
        if want("schema-contract"):
            report.findings.extend(check_schema(m, project))
        if want("knob-registry"):
            report.findings.extend(check_knobs(m, project, read_patterns))
        if any(want(r) for r in GENERIC_RULES):
            report.findings.extend(
                f for f in check_generic(m) if want(f.rule))
    if want("knob-registry"):
        report.findings.extend(
            check_knob_registry_side(project, read_patterns))

    seen: Set[Tuple] = set()
    deduped: List[Finding] = []
    for f in report.findings:
        k = f.key() + (f.line,)
        if k not in seen:
            seen.add(k)
            deduped.append(f)
    report.findings = deduped

    if baseline:
        entries = load_baseline(baseline)
        # path scope = the scan ROOTS, not just files that still exist:
        # an entry for a deleted file under a scanned directory must go
        # stale (so the baseline shrinks), while entries outside a
        # subset invocation's roots are merely carried
        scan_roots: List[str] = []
        for p in paths:
            ap = p if os.path.isabs(p) else os.path.join(root, p)
            rp = os.path.relpath(ap, root).replace(os.sep, "/")
            scan_roots.append(rp + "/" if os.path.isdir(ap) else rp)

        def path_in_scope(ep: str) -> bool:
            return any(ep == r or (r.endswith("/") and ep.startswith(r))
                       for r in scan_roots)

        in_scope = [(rules is None or e["rule"] in rules)
                    and path_in_scope(e["path"]) for e in entries]
        matched = [False] * len(entries)
        kept: List[Finding] = []
        for f in report.findings:
            hit = False
            for i, e in enumerate(entries):
                # one entry suppresses at most ONE finding: a second
                # identical violation added later must surface, not
                # ride an existing justification
                if not matched[i] and (
                        e["rule"], e["path"], e["context"],
                        e["message"]) == f.key():
                    matched[i] = True
                    hit = True
                    break
            if hit:
                report.suppressed += 1
            else:
                kept.append(f)
        report.findings = kept
        # an entry is stale only when this run could have matched it:
        # its rule ran and its file was scanned.  Out-of-scope entries
        # are carried so subset invocations (--rules gen, single files)
        # neither fail on them nor destroy them on --write-baseline.
        report.matched_entries = [e for e, ok in zip(entries, matched)
                                  if ok]
        report.carried_entries = [e for e, sc in zip(entries, in_scope)
                                  if not sc]
        report.stale = [e for e, ok, sc in zip(entries, matched,
                                               in_scope)
                        if sc and not ok]
    report.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return report


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="apexlint",
        description="invariant-aware static analysis for the tpu-apex "
                    "fleet (pure stdlib ast, no jax import)")
    ap.add_argument("paths", nargs="*",
                    default=["pytorch_distributed_tpu", "tools"])
    ap.add_argument("--root", default=None,
                    help="repo root (README/TESTING + relpaths); "
                         "default cwd")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (default: "
                         "tools/apexlint_baseline.json when present)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore any baseline file")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run ('gen' = the "
                         "generic pass, 'apex' = the invariant rules)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--allow-stale", action="store_true",
                    help="stale baseline entries warn instead of fail")
    ap.add_argument("--write-baseline", default=None, metavar="FILE",
                    help="write current findings as a baseline skeleton "
                         "(justifications must then be filled in)")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule, doc in RULES.items():
            print(f"{rule:22s} {doc}")
        return 0

    rules: Optional[Set[str]] = None
    if args.rules:
        rules = set()
        for r in args.rules.split(","):
            r = r.strip()
            if r == "gen":
                rules.update(GENERIC_RULES)
            elif r == "apex":
                rules.update(k for k in RULES
                             if k not in GENERIC_RULES)
            elif r in RULES:
                rules.add(r)
            else:
                print(f"apexlint: unknown rule '{r}'", file=sys.stderr)
                return 2
        rules.add("parse-error")

    root = os.path.abspath(args.root or os.getcwd())
    baseline = args.baseline
    if baseline is None and not args.no_baseline:
        default = os.path.join(root, "tools", "apexlint_baseline.json")
        if os.path.exists(default):
            baseline = default
    if args.no_baseline:
        baseline = None

    try:
        report = run(args.paths, root=root, baseline=baseline,
                     rules=rules)
    except BaselineError as e:
        print(f"apexlint: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        # still-matching and out-of-scope entries keep their written
        # justifications; only NEW findings get TODO skeletons
        entries = report.matched_entries + report.carried_entries + [
            dict(rule=f.rule, path=f.path, context=f.context,
                 message=f.message,
                 justification="TODO: justify or fix")
            for f in report.findings]
        with open(args.write_baseline, "w") as fh:
            json.dump({"entries": entries}, fh, indent=2,
                      ensure_ascii=False)
            fh.write("\n")
        print(f"apexlint: wrote {len(entries)} baseline entries "
              f"({len(report.findings)} new) to {args.write_baseline} "
              f"— fill in every TODO justification")
        return 1 if report.findings else 0

    if args.as_json:
        print(json.dumps(report.to_json(), indent=2))
    else:
        for f in report.findings:
            print(f.format())
        for e in report.stale:
            print(f"{e['path']} · baseline-stale · {e['rule']} entry no "
                  f"longer matches: {e['message'][:60]}")
        print(f"apexlint: {report.files} files, "
              f"{len(report.findings)} findings, "
              f"{report.suppressed} baselined, "
              f"{len(report.stale)} stale baseline entries")
    if report.findings:
        return 1
    if report.stale and not args.allow_stale:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
