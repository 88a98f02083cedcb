"""Whole-program wire-protocol & conformance checker for ray_tpu.

The per-file linter (``ray_tpu.devtools.lint``) catches local patterns;
this tool checks the contracts that span modules — exactly the bug
classes every review-hardening round since PR 6 has re-found by hand: a
sent verb whose handler arity drifted, a new verb sent to a peer that
never advertised the capability, a worker spawn path that stopped
consuming the derived config env, a counter incremented but never surfaced,
and lock nesting that contradicts a documented independent-leaf
convention.  The reference makes these impossible by construction (22
proto files under ``src/ray/protobuf/``); our contract is tuple literals
dispatched via ``msg[0] ==`` chains, so this tool recovers the schema
statically and diffs every site against the one catalog
(``ray_tpu._private.protocol.VERBS``).

Usage::

    python -m ray_tpu.devtools.protocheck ray_tpu/ tests/
    python -m ray_tpu.devtools.protocheck --doc          # catalog table
    python -m ray_tpu.devtools.protocheck --dump ray_tpu/  # inventory
    python -m ray_tpu.devtools.protocheck --select=RTL505 ray_tpu/

Findings print as ``path:line:col: RTLxxx message`` and the process
exits non-zero when any un-suppressed finding remains.  Suppression is
the linter's: ``# noqa: RTL501 -- reason`` on the anchored line — and
for the protocheck rule family the reason is MANDATORY (a reasonless
RTL5xx suppression is itself a finding, RTL500).

How sites are found
===================

SEND sites: tuple literals whose first element is a lowercase string
verb, flowing into a send carrier — ``protocol.send``/``send_batch``,
``self._send``/``_send_wire``/``_queue_send``/``head_send``/``.send``,
a conflation-buffer ``append``/``appendleft``, or a message-builder
``lambda``.  The sender's ROLE comes from the defining module (head =
``runtime.py``/``head_main.py``, worker = ``worker_main.py`` +
``direct.py``, client = ``client.py``, agent = ``node_agent.py``,
object server = ``object_transfer.py``/``shm_store.py``); other
ray_tpu modules are role-free senders (checked for verb existence and
arity, exempt from role rules), and test files never keep a handler
alive.  A module can override with a ``# protocheck: role=<role>``
comment in its first lines (fixtures use this).

HANDLE sites: ``msg[0] == "verb"`` / ``tag == "verb"`` chains (``tag``
assigned from ``msg[0]``), including ``assert msg[0] == "verb"``
handshakes.  The guarded block's subscript reach (``msg[i]``), exact
tuple unpacks (``_tag, a, b = msg``) and ``len(msg)`` guards give the
handler's arity requirements.

Rule catalog
============

RTL500  reasonless-suppression
    A ``# noqa: RTL5xx`` without a ``-- reason`` tail.  Protocol-level
    suppressions document a contract exception; the reason is the
    documentation.

RTL501  wire-verb conformance
    A sent verb missing from the catalog (typo or undocumented), a verb
    sent by a role the catalog does not list as a sender, a handler for
    an uncataloged verb or in a role the catalog does not list, a verb
    with in-tree senders but NO handler in any analyzed handler-role
    module, and a dead handler (no in-tree sender, verb not marked
    ``external``).

RTL502  wire-arity conformance
    A sender tuple whose arity falls outside the catalog range; a
    handler whose exact unpack or subscript reach contradicts the
    catalog; a handler that reads an optional element (index beyond the
    shortest legal form) without a ``len(msg)`` guard while some sender
    ships the short form — anchored with BOTH file:line ends.

RTL503  capability gating
    A send of a caps-gated verb (the negotiated ``object_caps`` /
    v1-lease families) from a function that is not capability-gated:
    neither the function nor (transitively, via intra-module callers)
    any path into it tests caps membership.  Pins the PR 3/6/7 "never
    probe an old peer" convention.

RTL504  spawn-env & counter plumbing
    A spawn path that stopped consuming ``_worker_config_env`` (the map
    itself is derived from ``Config`` and cannot go stale); a
    worker-side xfer-stats counter the head's aggregator drops; an
    aggregated counter ``transfer_stats()`` never surfaces.

RTL505  static lock-order inference
    The ``with self.<lock>:`` nesting graph across method bodies (one
    level of call resolution: ``self.m()``, ``self.attr.m()`` with the
    attr's class inferred from its constructor assignment, module
    functions — across all analyzed modules).  Locks created with a
    ``# lock-order: leaf`` annotation are the documented independent
    leaves: nesting INTO a leaf is the convention, any acquisition
    UNDER a leaf is a violation, and an edge into a non-leaf lock is
    undeclared nesting (annotate the target as a leaf, or suppress with
    a reason).  Catches statically what the runtime lockcheck only sees
    if the path executes.  Lexical heuristic: locks reached through
    unresolvable receivers are not seen.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu.devtools.lint import Finding, _attr_chain, _iter_py_files

RULES: Dict[str, str] = {
    "RTL500": "protocheck suppression without a '-- reason' tail",
    "RTL501": "wire verb unknown to the catalog, sent/handled by the "
              "wrong role, sent with no handler, or handled dead",
    "RTL502": "wire tuple arity contradicts the catalog or another "
              "module's sender/handler",
    "RTL503": "caps-gated verb sent from a function with no capability "
              "gate on any path into it",
    "RTL504": "spawn path not consuming _worker_config_env, or a stats "
              "counter dropped before transfer_stats()",
    "RTL505": "undeclared lock nesting, or a lock acquired under a "
              "documented independent leaf",
}

# Module basename -> wire role (the ISSUE's attribution table).
MODULE_ROLES: Dict[str, str] = {
    "runtime.py": "head",
    "head_main.py": "head",
    "worker_main.py": "worker",
    "direct.py": "worker",
    "client.py": "client",
    "node_agent.py": "agent",
    "object_transfer.py": "objsrv",
    "shm_store.py": "objsrv",
}

# Object descriptors ride inside messages and share the tuple-with-a-
# string-head shape; they are payload, not verbs.  "head"/"lease" are
# direct.py's outbound-routing wrappers (their PAYLOAD tuples are the
# send sites) and "ref" is the argument-encoding marker inside specs.
DESCRIPTOR_KINDS = {"inline", "shm", "parts", "spilled", "error", "ref"}
ROUTING_TAGS = {"head", "lease"}

_VERB_RE = re.compile(r"^[a-z][a-z0-9_]*$")
_LOCKISH_RE = re.compile(r"lock|cond|(^|_)cv$|(^|_)sem($|_)")
_CAPS_RE = re.compile(r"caps", re.IGNORECASE)
_ROLE_MARK_RE = re.compile(r"#\s*protocheck:\s*role=([a-z_]+)")
_STANDS_FOR_RE = re.compile(r"#\s*protocheck:\s*stands-for=([a-z_.]+)")
_LEAF_MARK_RE = re.compile(r"#\s*lock-order:\s*leaf\b")
_NOQA_RE = re.compile(r"#\s*noqa:\s*([A-Z0-9, ]+)(--\s*(.*))?")

# A send carrier is any callee whose name smells like a socket write or
# a message queue (protocol.send/send_batch, _send/_send_wire,
# _queue_send, head_send, worker_send_safe, queue_msg,
# _queue_small_put...); conflation-buffer appends count only inside
# role-attributed protocol modules (role-free library code appends
# plenty of non-wire tuples).
_SEND_CALLEE_RE = re.compile(r"send|queue")
BUFFER_CALLEES = {"append", "appendleft"}

LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                  "BoundedSemaphore"}


def _load_catalog():
    from ray_tpu._private import protocol

    return getattr(protocol, "VERBS", {})


class _SendSite:
    __slots__ = ("path", "line", "col", "verb", "lo", "hi", "role",
                 "fn", "is_test")

    def __init__(self, path, line, col, verb, lo, hi, role, fn, is_test):
        self.path, self.line, self.col = path, line, col
        self.verb, self.lo, self.hi = verb, lo, hi  # hi None = open-ended
        self.role, self.fn, self.is_test = role, fn, is_test


class _HandleSite:
    __slots__ = ("path", "line", "col", "verb", "reach", "exact",
                 "len_guarded", "role", "is_test")

    def __init__(self, path, line, col, verb, reach, exact, len_guarded,
                 role, is_test):
        self.path, self.line, self.col, self.verb = path, line, col, verb
        self.reach = reach            # 1 + max constant subscript index
        self.exact = exact            # arity pinned by a strict unpack
        self.len_guarded = len_guarded
        self.role, self.is_test = role, is_test


class _Fn:
    """One function/method def, for the caps-gating fixpoint."""
    __slots__ = ("module", "name", "node", "mentions_caps", "calls",
                 "parent")

    def __init__(self, module, name, node, parent=None):
        self.module, self.name, self.node = module, name, node
        self.mentions_caps = False
        self.calls: Set[str] = set()
        self.parent = parent  # lexically enclosing _Fn (closures)


class _ClassInfo:
    __slots__ = ("module", "name", "node", "bases", "methods",
                 "lock_attrs", "attr_types")

    def __init__(self, module, name, node, bases):
        self.module, self.name, self.node = module, name, node
        self.bases = bases                  # base-class name strings
        self.methods: Dict[str, ast.AST] = {}
        # lock attr name -> (line, declared-leaf?)
        self.lock_attrs: Dict[str, Tuple[int, bool]] = {}
        # self.<attr> = ClassName(...) -> attr -> ClassName
        self.attr_types: Dict[str, str] = {}


class _Module:
    def __init__(self, path: str, source: str, tree: ast.Module):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        base = os.path.basename(path)
        self.is_test = (base.startswith("test_")
                        or (os.sep + "tests" + os.sep) in path)
        self.role: Optional[str] = MODULE_ROLES.get(base)
        # Fixtures impersonate special modules: `# protocheck: role=X`
        # assigns a wire role, `# protocheck: stands-for=runtime.py`
        # makes the RTL504 pass treat the file as that module.
        self.stands_for: Optional[str] = None
        for line in self.lines[:10]:
            m = _ROLE_MARK_RE.search(line)
            if m:
                self.role = m.group(1)
                self.is_test = False
            m = _STANDS_FOR_RE.search(line)
            if m:
                self.stands_for = m.group(1)
                self.is_test = False
        self.sends: List[_SendSite] = []
        self.handles: List[_HandleSite] = []
        self.fns: List[_Fn] = []
        self.classes: List[_ClassInfo] = []
        # module-level lock names -> (line, leaf?)
        self.module_locks: Dict[str, Tuple[int, bool]] = {}

    def line_has_leaf_mark(self, lineno: int) -> bool:
        for ln in (lineno, lineno - 1):
            if 1 <= ln <= len(self.lines) \
                    and _LEAF_MARK_RE.search(self.lines[ln - 1]):
                return True
        return False


# ---------------------------------------------------------------- parse --

def _tuple_verb(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Tuple) and node.elts \
            and isinstance(node.elts[0], ast.Constant) \
            and isinstance(node.elts[0].value, str):
        verb = node.elts[0].value
        if _VERB_RE.match(verb) and verb not in DESCRIPTOR_KINDS \
                and verb not in ROUTING_TAGS:
            return verb
    return None


def _tuple_arity(node: ast.Tuple,
                 parent_binop: bool) -> Tuple[int, Optional[int]]:
    n = 0
    open_ended = parent_binop
    for elt in node.elts:
        if isinstance(elt, ast.Starred):
            open_ended = True
        else:
            n += 1
    return n, (None if open_ended else n)


def _is_lock_factory(value: ast.AST) -> bool:
    if not isinstance(value, ast.Call):
        return False
    chain = _attr_chain(value.func)
    return bool(chain) and chain[-1] in LOCK_FACTORIES


class _Extractor(ast.NodeVisitor):
    """One pass per module: send sites, handle sites, function graph,
    class/lock model."""

    def __init__(self, mod: _Module):
        self.mod = mod
        self.fn_stack: List[_Fn] = []
        self.class_stack: List[_ClassInfo] = []
        # verb tuples already claimed by a carrier (avoid double counting
        # the same literal through nested visits)
        self.claimed: Set[int] = set()

    # -- scope ------------------------------------------------------------
    def visit_ClassDef(self, node: ast.ClassDef):
        bases = []
        for b in node.bases:
            chain = _attr_chain(b)
            if chain:
                bases.append(chain[-1])
        info = _ClassInfo(self.mod, node.name, node, tuple(bases))
        self.mod.classes.append(info)
        self.class_stack.append(info)
        try:
            self.generic_visit(node)
        finally:
            self.class_stack.pop()

    def _visit_fn(self, node):
        fn = _Fn(self.mod, node.name, node,
                 parent=self.fn_stack[-1] if self.fn_stack else None)
        self.mod.fns.append(fn)
        if self.class_stack and node in self.class_stack[-1].node.body:
            self.class_stack[-1].methods[node.name] = node
        self.fn_stack.append(fn)
        try:
            self._scan_handler_arms(node)
            self.generic_visit(node)
        finally:
            self.fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    # -- caps tests --------------------------------------------------------
    # A function is capability-gated only when it TESTS caps — a
    # membership check (`"fetch_range" in caps`), a caps attribute in a
    # branch condition (`if not worker.lease_caps`), or a predicate call
    # (`peer_accepts_puts(caps)`) in a test position.  Merely receiving
    # or forwarding a ``caps`` value does not count: that is how the
    # un-gated bug looks.
    @staticmethod
    def _capsish(tree: ast.AST) -> bool:
        for sub in ast.walk(tree):
            if isinstance(sub, ast.Name) and _CAPS_RE.search(sub.id):
                return True
            if isinstance(sub, ast.Attribute) and _CAPS_RE.search(sub.attr):
                return True
            if isinstance(sub, ast.Call):
                chain = _attr_chain(sub.func)
                if chain and re.search(r"caps|accepts", chain[-1]):
                    return True
        return False

    def _note_caps_test(self, test: ast.AST):
        if self.fn_stack and self._capsish(test):
            self.fn_stack[-1].mentions_caps = True

    def visit_If(self, node: ast.If):
        self._note_caps_test(node.test)
        self.generic_visit(node)

    def visit_While(self, node: ast.While):
        self._note_caps_test(node.test)
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp):
        self._note_caps_test(node.test)
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert):
        self._note_caps_test(node.test)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare):
        # Membership tests outside an If (e.g. `ok = v in caps`) still
        # gate: the branch may live one expression away.
        if self.fn_stack and any(isinstance(op, (ast.In, ast.NotIn))
                                 for op in node.ops) \
                and any(self._capsish(c) for c in node.comparators):
            self.fn_stack[-1].mentions_caps = True
        self.generic_visit(node)

    # -- assignments: lock creation, attr types --------------------------
    def visit_Assign(self, node: ast.Assign):
        for target in node.targets:
            chain = _attr_chain(target)
            if not chain:
                continue
            if len(chain) == 2 and chain[0] == "self" and self.class_stack:
                cls = self.class_stack[-1]
                if _is_lock_factory(node.value):
                    cls.lock_attrs[chain[1]] = (
                        node.lineno,
                        self.mod.line_has_leaf_mark(node.lineno))
                elif isinstance(node.value, ast.Call):
                    cchain = _attr_chain(node.value.func)
                    if cchain and cchain[-1][:1].isupper():
                        cls.attr_types[chain[1]] = cchain[-1]
            elif len(chain) == 1 and not self.fn_stack \
                    and not self.class_stack \
                    and _is_lock_factory(node.value):
                self.mod.module_locks[chain[0]] = (
                    node.lineno, self.mod.line_has_leaf_mark(node.lineno))
        self.generic_visit(node)

    # -- calls: send carriers + call graph -------------------------------
    def visit_Call(self, node: ast.Call):
        chain = _attr_chain(node.func)
        leaf = chain[-1] if chain else None
        if self.fn_stack and leaf:
            self.fn_stack[-1].calls.add(leaf)
        carrier = leaf is not None and bool(_SEND_CALLEE_RE.search(leaf))
        buffered = (leaf in BUFFER_CALLEES and self.mod.role is not None
                    and not self.mod.is_test)
        if carrier or buffered:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                self._claim_verb_tuples(arg)
        self.generic_visit(node)

    def visit_Lambda(self, node: ast.Lambda):
        # Message-builder lambdas (("lease_req", rid, ...) factories).
        if not self.mod.is_test:
            self._claim_verb_tuples(node.body)
        self.generic_visit(node)

    def _claim_verb_tuples(self, root: ast.AST):
        """Find verb tuples in an argument subtree: through ternaries,
        concatenation, list literals, and the elements of routing
        wrappers / other claimed tuples (direct.py parks messages as
        ("head", msg) / ("lease", lease, msg, fallback)) — but not
        through nested calls."""
        stack = [(root, False)]
        while stack:
            node, in_binop = stack.pop()
            if isinstance(node, ast.Tuple):
                verb = _tuple_verb(node)
                if verb is not None and id(node) not in self.claimed:
                    self.claimed.add(id(node))
                    lo, hi = _tuple_arity(node, in_binop)
                    self.mod.sends.append(_SendSite(
                        self.mod.path, node.lineno, node.col_offset,
                        verb, lo, hi, self.mod.role,
                        self.fn_stack[-1] if self.fn_stack else None,
                        self.mod.is_test))
                # Nested payload tuples (routing wrappers, batched
                # message lists) are send sites of their own.
                stack += [(e, False) for e in node.elts[1:]]
            elif isinstance(node, ast.IfExp):
                stack += [(node.body, in_binop), (node.orelse, in_binop)]
            elif isinstance(node, ast.BinOp):
                stack += [(node.left, True), (node.right, True)]
            elif isinstance(node, (ast.List, ast.Set)):
                stack += [(e, in_binop) for e in node.elts]

    # -- handler arms -----------------------------------------------------
    def _scan_handler_arms(self, fn_node):
        """Within one function: find tag variables (``tag = msg[0]``),
        then every ``== "verb"`` guard and its block's subscript reach."""
        tagvars: Dict[str, str] = {}   # tag var -> msg var
        for stmt in ast.walk(fn_node):
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and stmt is not fn_node:
                continue
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and isinstance(stmt.value, ast.Subscript) \
                    and isinstance(stmt.value.value, ast.Name):
                sl = stmt.value.slice
                if isinstance(sl, ast.Constant) and sl.value == 0:
                    tagvars[stmt.targets[0].id] = stmt.value.value.id

        def compare_verbs(test) -> Tuple[Optional[str], List[str]]:
            """(msg var, verbs) when this test is a tag == "verb" (or
            or-chain / membership) guard."""
            verbs: List[str] = []
            msg_var: Optional[str] = None
            comps = []
            if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.Or):
                comps = test.values
            else:
                comps = [test]
            for comp in comps:
                if not isinstance(comp, ast.Compare) \
                        or len(comp.ops) != 1:
                    return None, []
                left, op, right = comp.left, comp.ops[0], \
                    comp.comparators[0]
                var = None
                if isinstance(left, ast.Name) and left.id in tagvars:
                    var = tagvars[left.id]
                elif isinstance(left, ast.Subscript) \
                        and isinstance(left.value, ast.Name) \
                        and isinstance(left.slice, ast.Constant) \
                        and left.slice.value == 0:
                    var = left.value.id
                if var is None:
                    return None, []
                vs = []
                if isinstance(op, ast.Eq) and isinstance(right, ast.Constant) \
                        and isinstance(right.value, str):
                    vs = [right.value]
                elif isinstance(op, ast.In) \
                        and isinstance(right, (ast.Tuple, ast.List, ast.Set)):
                    for e in right.elts:
                        if isinstance(e, ast.Constant) \
                                and isinstance(e.value, str):
                            vs.append(e.value)
                if not vs:
                    return None, []
                if msg_var is None:
                    msg_var = var
                verbs.extend(vs)
            return msg_var, verbs

        def is_nested_arm(stmt, msg_var: str) -> bool:
            """An inner If that re-dispatches on the same message var
            (multi-verb arms like the job_* family): its subscripts
            belong to ITS verbs, not the outer arm's."""
            if not isinstance(stmt, ast.If):
                return False
            for sub in ast.walk(stmt.test):
                if isinstance(sub, ast.Compare):
                    left = sub.left
                    if isinstance(left, ast.Name) \
                            and tagvars.get(left.id) == msg_var:
                        return True
                    if isinstance(left, ast.Subscript) \
                            and isinstance(left.value, ast.Name) \
                            and left.value.id == msg_var \
                            and isinstance(left.slice, ast.Constant) \
                            and left.slice.value == 0:
                        return True
            return False

        def block_reach(body: List[ast.stmt], msg_var: str,
                        top_level: bool = True):
            reach, exact, guarded = 0, None, False
            stack = list(body)
            while stack:
                sub = stack.pop()
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                    continue
                if top_level and is_nested_arm(sub, msg_var):
                    continue  # its subscripts belong to the inner arms
                if isinstance(sub, ast.Subscript) \
                        and isinstance(sub.value, ast.Name) \
                        and sub.value.id == msg_var \
                        and isinstance(sub.slice, ast.Constant) \
                        and isinstance(sub.slice.value, int):
                    reach = max(reach, sub.slice.value + 1)
                elif isinstance(sub, ast.Assign) \
                        and len(sub.targets) == 1 \
                        and isinstance(sub.targets[0], ast.Tuple) \
                        and isinstance(sub.value, ast.Name) \
                        and sub.value.id == msg_var:
                    elts = sub.targets[0].elts
                    if any(isinstance(e, ast.Starred) for e in elts):
                        reach = max(
                            reach,
                            sum(1 for e in elts
                                if not isinstance(e, ast.Starred)))
                    else:
                        exact = len(elts)
                elif isinstance(sub, ast.Call):
                    cchain = _attr_chain(sub.func)
                    if cchain == ["len"] and sub.args \
                            and isinstance(sub.args[0], ast.Name) \
                            and sub.args[0].id == msg_var:
                        guarded = True
                stack.extend(ast.iter_child_nodes(sub))
            return reach, exact, guarded

        def scan_stmts(stmts: List[ast.stmt]):
            for i, stmt in enumerate(stmts):
                if isinstance(stmt, ast.If):
                    msg_var, verbs = compare_verbs(stmt.test)
                    if msg_var and verbs:
                        guard_has_len = any(
                            isinstance(s, ast.Call)
                            and _attr_chain(s.func) == ["len"]
                            for s in ast.walk(stmt.test))
                        reach, exact, guarded = block_reach(
                            stmt.body, msg_var)
                        for verb in verbs:
                            if verb in DESCRIPTOR_KINDS \
                                    or verb in ROUTING_TAGS:
                                continue
                            self.mod.handles.append(_HandleSite(
                                self.mod.path, stmt.lineno,
                                stmt.col_offset, verb, reach, exact,
                                guarded or guard_has_len, self.mod.role,
                                self.mod.is_test))
                    scan_stmts(stmt.body)
                    scan_stmts(stmt.orelse)
                elif isinstance(stmt, ast.Assert):
                    msg_var, verbs = compare_verbs(stmt.test)
                    if msg_var and verbs:
                        reach, exact, guarded = block_reach(
                            stmts[i + 1:], msg_var)
                        for verb in verbs:
                            if verb in DESCRIPTOR_KINDS \
                                    or verb in ROUTING_TAGS:
                                continue
                            self.mod.handles.append(_HandleSite(
                                self.mod.path, stmt.lineno,
                                stmt.col_offset, verb, reach, exact,
                                guarded, self.mod.role,
                                self.mod.is_test))
                elif isinstance(stmt, (ast.For, ast.While, ast.With,
                                       ast.Try)):
                    for attr in ("body", "orelse", "finalbody"):
                        scan_stmts(getattr(stmt, attr, []) or [])
                    for h in getattr(stmt, "handlers", []) or []:
                        scan_stmts(h.body)

        scan_stmts(fn_node.body)


# ------------------------------------------------------------- analysis --

class Analysis:
    def __init__(self, paths, catalog=None):
        self.catalog = _load_catalog() if catalog is None else catalog
        self.modules: List[_Module] = []
        self.findings: List[Finding] = []
        for path in _iter_py_files(paths):
            try:
                with open(path, "r", encoding="utf-8") as f:
                    source = f.read()
                tree = ast.parse(source, filename=path)
            except (OSError, SyntaxError):
                continue  # the lint gate owns syntax errors
            mod = _Module(path, source, tree)
            _Extractor(mod).visit(tree)
            self.modules.append(mod)

    # -- helpers ----------------------------------------------------------
    def _emit(self, path, line, col, rule, message):
        self.findings.append(Finding(path, line, col, rule, message))

    def run(self, select: Optional[Set[str]] = None) -> List[Finding]:
        self.findings = []
        self._check_verbs()
        self._check_caps()
        self._check_spawn_paths()
        self._check_counters()
        self._check_serve_counters()
        self._check_locks()
        # One edge/site can be reached through several call paths or
        # held-lock levels: report it once.
        seen: Set[str] = set()
        unique = []
        for f in self.findings:
            key = repr(f)
            if key not in seen:
                seen.add(key)
                unique.append(f)
        self.findings = unique
        kept = self._apply_suppressions()
        if select:
            kept = [f for f in kept
                    if any(f.rule.startswith(s) for s in select)]
        kept.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
        return kept

    def _apply_suppressions(self) -> List[Finding]:
        by_path = {m.path: m for m in self.modules}
        kept: List[Finding] = []
        flagged_noqa: Set[Tuple[str, int]] = set()
        for f in self.findings:
            mod = by_path.get(f.path)
            line = (mod.lines[f.line - 1]
                    if mod and f.line <= len(mod.lines) else "")
            m = _NOQA_RE.search(line)
            rules = set()
            if m:
                rules = {tok for tok in
                         re.split(r"[\s,]+", m.group(1).upper()) if tok}
            if m and f.rule in rules:
                reason = (m.group(3) or "").strip()
                if not reason and (f.path, f.line) not in flagged_noqa:
                    flagged_noqa.add((f.path, f.line))
                    kept.append(Finding(
                        f.path, f.line, f.col, "RTL500",
                        f"suppression of {f.rule} carries no '-- reason' "
                        f"tail; protocol exceptions must say why"))
                continue
            kept.append(f)
        return kept

    # -- RTL501/502: verbs ------------------------------------------------
    def _check_verbs(self):
        sends = defaultdict(list)
        handles = defaultdict(list)
        roles_present: Set[str] = set()
        for mod in self.modules:
            if mod.role and not mod.is_test:
                roles_present.add(mod.role)
            for s in mod.sends:
                sends[s.verb].append(s)
            for h in mod.handles:
                handles[h.verb].append(h)

        for verb, sites in sends.items():
            spec = self.catalog.get(verb)
            for s in sites:
                if spec is None:
                    self._emit(
                        s.path, s.line, s.col, "RTL501",
                        f"verb {verb!r} is not in the protocol catalog "
                        f"(protocol.VERBS) — typo, or add it with roles/"
                        f"arity/doc")
                    continue
                if s.is_test:
                    pass  # tests may impersonate any role
                elif s.role and s.role not in spec.senders:
                    self._emit(
                        s.path, s.line, s.col, "RTL501",
                        f"verb {verb!r} sent from role {s.role!r}; the "
                        f"catalog lists senders {spec.senders}")
                # Arity vs catalog.
                if spec.arity is not None:
                    lo, hi = spec.arity
                    if s.hi is not None and not (lo <= s.hi and s.lo <= hi):
                        self._emit(
                            s.path, s.line, s.col, "RTL502",
                            f"{verb!r} sent with arity {s.lo}; the "
                            f"catalog allows {lo}..{hi}")
                    elif s.hi is None and s.lo > hi:
                        self._emit(
                            s.path, s.line, s.col, "RTL502",
                            f"{verb!r} sent with arity >= {s.lo}; the "
                            f"catalog allows {lo}..{hi}")

        for verb, sites in handles.items():
            spec = self.catalog.get(verb)
            live_senders = [s for s in sends.get(verb, ())
                            if not s.is_test]
            for h in sites:
                if spec is None:
                    self._emit(
                        h.path, h.line, h.col, "RTL501",
                        f"handler for verb {verb!r} not in the protocol "
                        f"catalog (protocol.VERBS) — typo, or add it")
                    continue
                if h.is_test:
                    continue
                if h.role and h.role not in spec.handlers:
                    self._emit(
                        h.path, h.line, h.col, "RTL501",
                        f"verb {verb!r} handled in role {h.role!r}; the "
                        f"catalog lists handlers {spec.handlers}")
                if spec.arity is not None:
                    self._check_handler_arity(h, spec, live_senders)

        # Liveness: cross-module existence checks.
        for verb, spec in self.catalog.items():
            live_sends = [s for s in sends.get(verb, ())
                          if not s.is_test]
            live_handles = [h for h in handles.get(verb, ())
                            if not h.is_test]
            if live_sends and not live_handles and not spec.external \
                    and set(spec.handlers) & roles_present:
                s = live_sends[0]
                self._emit(
                    s.path, s.line, s.col, "RTL501",
                    f"verb {verb!r} is sent but NO analyzed module of "
                    f"roles {spec.handlers} handles it "
                    f"({len(live_sends)} send site(s))")
            if live_handles and not live_sends and not spec.external \
                    and set(spec.senders) & roles_present:
                h = live_handles[0]
                self._emit(
                    h.path, h.line, h.col, "RTL501",
                    f"dead handler: no analyzed module sends {verb!r} "
                    f"(catalog senders {spec.senders}); delete the arm "
                    f"or mark the verb external=True in the catalog")

    def _check_handler_arity(self, h: _HandleSite, spec, live_senders):
        lo, hi = spec.arity
        if h.exact is not None:
            if not (lo <= h.exact <= hi):
                self._emit(
                    h.path, h.line, h.col, "RTL502",
                    f"handler unpacks {h.verb!r} into exactly {h.exact} "
                    f"elements; the catalog allows {lo}..{hi}")
            elif h.exact < hi and not h.len_guarded:
                self._emit(
                    h.path, h.line, h.col, "RTL502",
                    f"handler unpacks {h.verb!r} into exactly {h.exact} "
                    f"elements without a len() guard, but the catalog "
                    f"allows up to {hi} — a longer legal message would "
                    f"crash the unpack")
        if h.reach > hi:
            self._emit(
                h.path, h.line, h.col, "RTL502",
                f"handler reads {h.verb}[{h.reach - 1}] but the catalog "
                f"caps arity at {hi}")
        elif h.reach > lo and not h.len_guarded:
            short = [s for s in live_senders
                     if s.hi is not None and s.hi < h.reach]
            if short:
                s = short[0]
                self._emit(
                    h.path, h.line, h.col, "RTL502",
                    f"handler reads optional element "
                    f"{h.verb}[{h.reach - 1}] without a len() guard, but "
                    f"{s.path}:{s.line} sends the {s.hi}-element form")

    # -- RTL503: caps gating ----------------------------------------------
    def _check_caps(self):
        # Fixpoint per module: a function is caps-gated if it mentions
        # caps itself, or every known intra-module caller is gated.
        for mod in self.modules:
            if mod.is_test:
                continue
            by_name = defaultdict(list)
            for fn in mod.fns:
                by_name[fn.name].append(fn)
            callers: Dict[int, Set[int]] = defaultdict(set)
            for fn in mod.fns:
                for callee_name in fn.calls:
                    for callee in by_name.get(callee_name, ()):
                        if callee is not fn:
                            callers[id(callee)].add(id(fn))
                # A nested def runs on behalf of its enclosing function
                # (thread targets, deferred closures): the enclosing
                # gate covers it.
                if fn.parent is not None:
                    callers[id(fn)].add(id(fn.parent))
            gated = {id(fn): fn.mentions_caps for fn in mod.fns}
            changed = True
            while changed:
                changed = False
                for fn in mod.fns:
                    if gated[id(fn)]:
                        continue
                    cs = callers.get(id(fn))
                    if cs and all(gated.get(c, False) for c in cs):
                        gated[id(fn)] = True
                        changed = True
            for s in mod.sends:
                spec = self.catalog.get(s.verb)
                if spec is None or not spec.caps:
                    continue
                if s.fn is None or not gated.get(id(s.fn), False):
                    self._emit(
                        s.path, s.line, s.col, "RTL503",
                        f"caps-gated verb {s.verb!r} ({spec.caps}) sent "
                        f"with no capability test on any path into "
                        f"{s.fn.name if s.fn else '<module>'}() — old "
                        f"peers must never see it (PR 3/6/7 convention)")

    # -- RTL504: spawn env + counters ------------------------------------
    def _find_module(self, basename: str) -> Optional[_Module]:
        for mod in self.modules:
            if not mod.is_test \
                    and (os.path.basename(mod.path) == basename
                         or mod.stands_for == basename):
                return mod
        return None

    def _check_spawn_paths(self):
        """Both spawn paths must consume _worker_config_env."""
        rt = self._find_module("runtime.py")
        if rt is None \
                or not any(fn.name == "_worker_config_env"
                           for fn in rt.fns):
            return
        for fn in rt.fns:
            if fn.name in ("_spawn_worker", "_spawn_worker_via_agent") \
                    and "_worker_config_env" not in fn.calls:
                self._emit(
                    rt.path, fn.node.lineno, fn.node.col_offset,
                    "RTL504",
                    f"spawn path {fn.name}() does not consume "
                    f"_worker_config_env() — knobs will reach only "
                    f"the other spawn path")

    def _check_counters(self):
        rt = self._find_module("runtime.py")
        if rt is None:
            return
        # A: keys the head's xfer_stats handler aggregates (d.get("k")),
        # located via the handler arm protocheck already extracted.
        agg: Dict[str, int] = {}
        agg_line = None
        for h in rt.handles:
            if h.verb == "xfer_stats":
                agg_line = h.line
        if agg_line is None:
            return
        # Collect d.get("key") string constants near the handler line.
        for fn in rt.fns:
            node = fn.node
            if not (node.lineno <= agg_line <= (node.end_lineno or 0)):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr == "get" and sub.args \
                        and isinstance(sub.args[0], ast.Constant) \
                        and isinstance(sub.args[0].value, str) \
                        and sub.lineno >= agg_line \
                        and sub.lineno <= agg_line + 40:
                    agg[sub.args[0].value] = sub.lineno
        if not agg:
            return
        # T: keys surfaced by transfer_stats().
        surfaced: Set[str] = set()
        for fn in rt.fns:
            if fn.name != "transfer_stats":
                continue
            for sub in ast.walk(fn.node):
                if isinstance(sub, ast.Dict):
                    for k in sub.keys:
                        if isinstance(k, ast.Constant) \
                                and isinstance(k.value, str):
                            surfaced.add(k.value)
        for key, line in agg.items():
            if key not in surfaced:
                self._emit(
                    rt.path, line, 0, "RTL504",
                    f"xfer_stats aggregates counter {key!r} but "
                    f"transfer_stats() never surfaces it")
        # W: worker-side stats() dicts that feed the xfer stream — any
        # stats() whose keys overlap the aggregated set must be fully
        # aggregated (a counter added to one is silently dropped
        # otherwise).
        for mod in self.modules:
            if mod.is_test or mod.role not in ("worker", "objsrv"):
                continue
            for fn in mod.fns:
                if fn.name != "stats":
                    continue
                keys = {}
                for sub in ast.walk(fn.node):
                    if isinstance(sub, ast.Dict):
                        for k in sub.keys:
                            if isinstance(k, ast.Constant) \
                                    and isinstance(k.value, str):
                                keys[k.value] = sub.lineno
                if not keys or not (set(keys) & set(agg)):
                    continue
                for key, line in keys.items():
                    if key not in agg:
                        self._emit(
                            mod.path, line, 0, "RTL504",
                            f"worker counter {key!r} rides the "
                            f"xfer_stats delta but the head's "
                            f"aggregator (runtime.py:{agg_line}) drops "
                            f"it — every shipped counter must reach "
                            f"transfer_stats()")

    def _check_serve_counters(self):
        """Serve-plane twin of _check_counters: every key a serve
        batcher's ``stats()`` ships (serve/batching.py,
        serve/continuous.py, and the kv engine's ``stats_locked()``,
        whose dict is merged into the batcher's) must SURVIVE the
        controller rollup — appear in ``serving_stats`` in
        serve/api.py, either read off a replica row (``b[...]`` /
        ``b.get(...)``) or recomputed into the aggregate dict.  A
        counter added to a batcher but dropped by the rollup is
        invisible at ``serve.serving_stats()`` — exactly the bug class
        the xfer-stats rule pins for the head."""
        sep = os.sep
        api = None
        for mod in self.modules:
            if not mod.is_test and mod.path.endswith(
                    f"serve{sep}api.py"):
                api = mod
                break
        if api is None:
            return
        # Keys surviving the rollup: string constants subscripted /
        # .get()'d / assigned anywhere inside serving_stats defs, plus
        # dict-literal keys (the aggregate's shape).
        survived: Set[str] = set()
        roll_line = None
        for fn in api.fns:
            if fn.name != "serving_stats":
                continue
            roll_line = roll_line or fn.node.lineno
            for sub in ast.walk(fn.node):
                if isinstance(sub, ast.Subscript) \
                        and isinstance(sub.slice, ast.Constant) \
                        and isinstance(sub.slice.value, str):
                    survived.add(sub.slice.value)
                elif isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr == "get" and sub.args \
                        and isinstance(sub.args[0], ast.Constant) \
                        and isinstance(sub.args[0].value, str):
                    survived.add(sub.args[0].value)
                elif isinstance(sub, (ast.Dict, ast.Tuple)):
                    for k in (sub.keys if isinstance(sub, ast.Dict)
                              else sub.elts):
                        if isinstance(k, ast.Constant) \
                                and isinstance(k.value, str):
                            survived.add(k.value)
        if roll_line is None:
            return
        for mod in self.modules:
            if mod.is_test or f"{sep}serve{sep}" not in mod.path \
                    or mod.path.endswith(f"serve{sep}api.py"):
                continue
            for fn in mod.fns:
                if fn.name not in ("stats", "stats_locked"):
                    continue
                for sub in ast.walk(fn.node):
                    if not isinstance(sub, ast.Dict):
                        continue
                    for k in sub.keys:
                        if isinstance(k, ast.Constant) \
                                and isinstance(k.value, str) \
                                and k.value not in survived:
                            self._emit(
                                mod.path, sub.lineno, 0, "RTL504",
                                f"serve batcher counter {k.value!r} is "
                                f"dropped by the controller rollup "
                                f"(serve/api.py:{roll_line} "
                                f"serving_stats) — every shipped "
                                f"counter must survive head "
                                f"aggregation")

    # -- RTL505: lock order -----------------------------------------------
    def _check_locks(self):
        # Global class registry (unique names only — ambiguous names are
        # skipped rather than guessed).
        registry: Dict[str, _ClassInfo] = {}
        ambiguous: Set[str] = set()
        for mod in self.modules:
            if mod.is_test:
                continue
            for cls in mod.classes:
                if cls.name in registry:
                    ambiguous.add(cls.name)
                registry[cls.name] = cls
        for name in ambiguous:
            registry.pop(name, None)

        def resolve_cls(cls: _ClassInfo) -> List[_ClassInfo]:
            """cls + base classes (by unique name)."""
            out, seen = [cls], {cls.name}
            queue = list(cls.bases)
            while queue:
                b = queue.pop()
                if b in seen:
                    continue
                seen.add(b)
                info = registry.get(b)
                if info is not None:
                    out.append(info)
                    queue += list(info.bases)
            return out

        def lock_id(cls: Optional[_ClassInfo], mod: _Module, attr: str):
            if cls is not None:
                for c in resolve_cls(cls):
                    if attr in c.lock_attrs:
                        line, leaf = c.lock_attrs[attr]
                        return (c.module.path, c.name, attr), leaf
                return (mod.path, cls.name, attr), False
            if attr in mod.module_locks:
                line, leaf = mod.module_locks[attr]
                return (mod.path, None, attr), leaf
            return None, False

        def entry_locks(cls: Optional[_ClassInfo], mod: _Module,
                        fn_node) -> List[Tuple[tuple, bool]]:
            """Locks a callee acquires lexically (not inside nested
            defs) — the one-level resolution target set."""
            out = []
            stack = list(fn_node.body)
            while stack:
                stmt = stack.pop()
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if isinstance(stmt, (ast.With, ast.AsyncWith)):
                    for item in stmt.items:
                        lid = self._with_lock_id(
                            item.context_expr, cls, mod, lock_id)
                        if lid is not None:
                            out.append((lid[0], lid[1], stmt.lineno))
                for child in ast.iter_child_nodes(stmt):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.Lambda)):
                        continue
                    stack.append(child)
            return out

        for mod in self.modules:
            if mod.is_test:
                continue
            method_nodes = set()
            for cls in mod.classes:
                for mname, mnode in cls.methods.items():
                    method_nodes.add(id(mnode))
                    self._walk_regions(mod, cls, mnode, [], registry,
                                       resolve_cls, lock_id, entry_locks)
            # Module-level (and nested) functions are region roots too —
            # the one module-level leaf in the tree (shm_store's
            # _copy_pool_lock) is only ever acquired in module
            # functions, so skipping them would make its leaf
            # declaration unenforceable.  Without a class context only
            # module-lock / module-function resolution applies.
            for fn in mod.fns:
                if id(fn.node) not in method_nodes \
                        and not isinstance(fn.node, ast.Lambda):
                    self._walk_regions(mod, None, fn.node, [], registry,
                                       resolve_cls, lock_id, entry_locks)

    def _with_lock_id(self, expr, cls, mod, lock_id):
        chain = _attr_chain(expr)
        if not chain:
            return None
        out = None
        if len(chain) == 2 and chain[0] == "self" \
                and _LOCKISH_RE.search(chain[1].lower()):
            out = lock_id(cls, mod, chain[1])
        elif len(chain) == 1 and chain[0] in mod.module_locks:
            out = lock_id(None, mod, chain[0])
        return out if out is not None and out[0] is not None else None

    def _walk_regions(self, mod, cls, node, held, registry, resolve_cls,
                      lock_id, entry_locks):
        """held: [(lock_id, leaf?)] currently-held with-locks."""
        for stmt in ast.iter_child_nodes(node):
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue  # runs at call time, not under this region
            acquired = None
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    lid = self._with_lock_id(item.context_expr, cls,
                                             mod, lock_id)
                    if lid is not None:
                        acquired = lid
                        self._note_edges(mod, held, lid, stmt.lineno)
            # Resolve calls appearing anywhere in this statement while
            # locks are held (one level).
            if held:
                for sub in ast.walk(stmt):
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef, ast.Lambda)):
                        continue
                    if not isinstance(sub, ast.Call):
                        continue
                    target = self._resolve_call(sub, cls, mod, registry,
                                                resolve_cls)
                    if target is None:
                        continue
                    tcls, tmod, tnode = target
                    for lid, leaf, _ln in entry_locks(tcls, tmod, tnode):
                        self._note_edges(mod, held, (lid, leaf),
                                         sub.lineno)
            if acquired is not None:
                held.append(acquired)
                self._walk_regions(mod, cls, stmt, held, registry,
                                   resolve_cls, lock_id, entry_locks)
                held.pop()
            else:
                self._walk_regions(mod, cls, stmt, held, registry,
                                   resolve_cls, lock_id, entry_locks)

    def _resolve_call(self, call: ast.Call, cls, mod, registry,
                      resolve_cls):
        chain = _attr_chain(call.func)
        if not chain:
            return None
        if len(chain) == 2 and chain[0] == "self" and cls is not None:
            for c in resolve_cls(cls):
                if chain[1] in c.methods:
                    return c, c.module, c.methods[chain[1]]
            return None
        if len(chain) == 3 and chain[0] == "self" and cls is not None:
            attr, meth = chain[1], chain[2]
            for c in resolve_cls(cls):
                tname = c.attr_types.get(attr)
                if tname and tname in registry:
                    target = registry[tname]
                    if meth in target.methods:
                        return (target, target.module,
                                target.methods[meth])
            return None
        if len(chain) == 1:
            for fn in mod.fns:
                if fn.name == chain[0] \
                        and isinstance(fn.node, (ast.FunctionDef,
                                                 ast.AsyncFunctionDef)):
                    # Module-level functions only (methods resolved via
                    # self above).
                    return None, mod, fn.node
        return None

    def _note_edges(self, mod: _Module, held, target, lineno: int):
        tid, tleaf = target
        for hid, hleaf in held:
            if hid == tid:
                continue  # re-entrant / same-lock
            if hleaf:
                self._emit(
                    mod.path, lineno, 0, "RTL505",
                    f"lock {_fmt_lock(tid)} acquired while holding "
                    f"{_fmt_lock(hid)}, which is declared an "
                    f"independent leaf ('# lock-order: leaf') — leaves "
                    f"must acquire nothing")
            elif not tleaf:
                self._emit(
                    mod.path, lineno, 0, "RTL505",
                    f"undeclared lock nesting: {_fmt_lock(tid)} "
                    f"acquired while holding {_fmt_lock(hid)} — declare "
                    f"the inner lock '# lock-order: leaf' at its "
                    f"creation site, or suppress here with a reason")

    # -- inventory dump ---------------------------------------------------
    def dump(self) -> str:
        out = []
        sends = defaultdict(list)
        handles = defaultdict(list)
        for mod in self.modules:
            for s in mod.sends:
                sends[s.verb].append(s)
            for h in mod.handles:
                handles[h.verb].append(h)
        for verb in sorted(set(sends) | set(handles)):
            out.append(f"== {verb}")
            for s in sends.get(verb, ()):
                hi = "open" if s.hi is None else s.hi
                out.append(f"  send   {s.role or '-':7} "
                           f"arity={s.lo}..{hi}  "
                           f"{s.path}:{s.line}"
                           f"{'  [test]' if s.is_test else ''}")
            for h in handles.get(verb, ()):
                out.append(
                    f"  handle {h.role or '-':7} reach={h.reach} "
                    f"exact={h.exact} lenguard={h.len_guarded}  "
                    f"{h.path}:{h.line}"
                    f"{'  [test]' if h.is_test else ''}")
        return "\n".join(out)


def _fmt_lock(lid: tuple) -> str:
    path, cls, attr = lid
    base = os.path.splitext(os.path.basename(path))[0]
    return f"{base}.{cls + '.' if cls else ''}{attr}"


# ------------------------------------------------------------------ doc --

def catalog_doc() -> str:
    """Markdown table of the wire-verb catalog (the README's generated
    wire-protocol section: `python -m ray_tpu.devtools.protocheck
    --doc`)."""
    catalog = _load_catalog()
    lines = [
        "| verb | senders | handlers | arity | caps | description |",
        "|---|---|---|---|---|---|",
    ]
    for verb in sorted(catalog):
        spec = catalog[verb]
        if spec.arity is None:
            arity = "var"
        elif spec.arity[0] == spec.arity[1]:
            arity = str(spec.arity[0])
        else:
            arity = f"{spec.arity[0]}..{spec.arity[1]}"
        lines.append(
            f"| `{verb}` | {', '.join(spec.senders)} "
            f"| {', '.join(spec.handlers)} | {arity} "
            f"| {spec.caps or ''} "
            f"| {spec.doc}{' *(external)*' if spec.external else ''} |")
    return "\n".join(lines)


def check_paths(paths, select: Optional[Set[str]] = None,
                catalog=None) -> List[Finding]:
    return Analysis(paths, catalog=catalog).run(select=select)


def main(argv=None) -> int:
    from ray_tpu.devtools.lint import run_cli

    argv = list(sys.argv[1:] if argv is None else argv)
    dump = "--dump" in argv
    if dump:
        argv.remove("--dump")

    def runner(paths, select):
        analysis = Analysis(paths)
        if dump:
            print(analysis.dump())
            return 0
        return analysis.run(select=select)

    return run_cli(
        argv, rules=RULES, doc=catalog_doc, runner=runner,
        usage="usage: python -m ray_tpu.devtools.protocheck "
              "[--doc|--dump|--list-rules] [--select=RTL5xx,...] "
              "PATH [PATH ...]")


if __name__ == "__main__":
    sys.exit(main())
