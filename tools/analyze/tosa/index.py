"""Phase-1 project index: per-module summaries for cross-module checkers.

One AST pass per file extracts a JSON-serializable :class:`ModuleSummary`
holding everything the phase-2 (project-wide) rules need:

- the module symbol table (imports, classes, functions) and a call graph
  in the form of per-function callee references,
- RacerD-style lock summaries: which locks each function acquires, which
  locks it acquires *while holding* another, and which calls happen under
  a held lock (``lock-order`` builds the global acquisition-order graph
  from these),
- bounded-queue attributes, thread spawn targets, and ``put``/``get``/
  ``join`` sites relative to held locks (the queue-deadlock pattern),
- obs metric registrations (kind, literal name, receiver) and private
  ``Registry`` lifecycles (``metrics-contract``),
- a small dataflow IR per function — ordered events over local names —
  for the ``donation-safety`` taint interpreter,
- chaos facts (fired sites, docstring site table) so ``chaos-obs-coverage``
  can run off the index when per-file walks are skipped (cache hits).

Summaries are plain dicts of JSON types so the whole index can be cached
on disk keyed by file content hash (:func:`load_cache`/:func:`save_cache`);
a warm run deserializes instead of re-parsing.
"""

import ast
import hashlib
import json
import os

from .core import dotted_name, root_name

#: constructors whose result is a lock-like object (threading.*)
LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore"}
#: attribute-name fragments that mark a lock even without a seen ctor
LOCK_NAME_HINTS = ("lock", "cond", "mutex")
#: constructors whose result is a queue
QUEUE_CTORS = {"Queue", "LifoQueue", "PriorityQueue", "SimpleQueue"}
#: calls that start a thread of execution with a target callable
SPAWN_CTORS = {"Thread", "Timer"}

#: calls whose result is a fresh host copy (clears donation/device taint)
CLEANING_CALLS = {"array", "copy", "deepcopy", "ascontiguousarray", "copy_to_host"}
#: in-place ndarray mutators (receiver method calls)
INPLACE_METHODS = {"fill", "sort", "resize", "partition", "put", "setflags", "itemset", "byteswap"}
#: container-growing methods on attribute receivers (pooling sinks)
POOL_METHODS = {"append", "extend", "add", "insert", "appendleft"}
#: calls that publish/merge a private registry into the cluster view
PUBLISH_CALLS = {"accumulate_to_channel", "publish_to_channel", "SnapshotPublisher"}

#: env-var name prefixes that form the cross-process communication lanes
#: (reservation REG, child spawn, worker fork, replica launch, bench attach)
ENV_LANE_PREFIXES = ("TOS_", "TF_CONFIG")
#: name fragments that mark a path expression as a staging/temporary file
TMP_NAME_HINTS = ("tmp", "temp", "stag", "part", "pending", "scratch")
#: name fragments in an `if` test that signal a loop's shutdown check
STOP_NAME_HINTS = ("stop", "shut", "clos", "done", "exit", "cancel", "running", "alive")


def module_name(relpath):
    """Dotted module name for a repo-relative path."""
    parts = relpath.replace(os.sep, "/").split("/")
    if parts[-1].endswith(".py"):
        parts[-1] = parts[-1][:-3]
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts) if parts else ""


def _literal_str(node):
    return node.value if isinstance(node, ast.Constant) and isinstance(node.value, str) else None


def _env_key(node):
    """An env-var key expression as a literal name, a ``$``-prefixed
    constant reference (resolved by phase 2 against module consts), or
    None when dynamic (f-strings, concatenation)."""
    lit = _literal_str(node)
    if lit is not None:
        return lit
    ref = dotted_name(node)
    if ref is not None:
        return "$" + ref
    return None


def _is_env_lane_literal(name):
    """True for a literal env-var name on the checked lanes."""
    return any(name.startswith(p) for p in ENV_LANE_PREFIXES)


def _name_has_tmp_hint(expr):
    """True when a path expression mentions a staging/temp name anywhere
    (variable names, attribute tails, or string literal fragments)."""
    for node in ast.walk(expr):
        text = None
        if isinstance(node, ast.Name):
            text = node.id
        elif isinstance(node, ast.Attribute):
            text = node.attr
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            text = node.value
        if text and any(h in text.lower() for h in TMP_NAME_HINTS):
            return True
    return False


def _name_has_dir_hint(expr):
    """True when a path expression names a directory (``dirname(...)``,
    ``self.root``, ``parent`` — the dir-fsync half of the commit idiom)."""
    for node in ast.walk(expr):
        text = None
        if isinstance(node, ast.Name):
            text = node.id
        elif isinstance(node, ast.Attribute):
            text = node.attr
        if text and any(h in text.lower() for h in ("dir", "root", "parent", "folder")):
            return True
    return False


def _is_chaos_test(test):
    """True when an ``if`` test consults the chaos plane — the guarded
    branch is a deliberately-torn write path, not a durability bug."""
    for node in ast.walk(test):
        if isinstance(node, (ast.Name, ast.Attribute)):
            d = dotted_name(node)
            if d and (d == "chaos" or d.startswith("chaos.")):
                return True
    return False


def _compare_is_none(node):
    """True for a ``x is None`` / ``x == None`` comparison node."""
    return (
        isinstance(node, ast.Compare)
        and any(isinstance(op, (ast.Is, ast.Eq)) for op in node.ops)
        and any(
            isinstance(c, ast.Constant) and c.value is None for c in node.comparators
        )
    )


def _body_has_exit(stmts):
    for s in stmts:
        for n in ast.walk(s):
            if isinstance(n, (ast.Return, ast.Break)):
                return True
            if isinstance(n, ast.Raise):
                return True
    return False


def _while_true_has_stop(body):
    """Does a ``while True`` body check a reachable stop signal?

    Recognized: ``Event.is_set()``/``.wait()`` anywhere; a queue-sentinel
    exit (``if item is None: return/break``); or an exit guarded by a test
    naming a stop-hint attribute (``if self._closed: break``)."""
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                d = dotted_name(node.func) or ""
                if d.split(".")[-1] in ("is_set", "wait"):
                    return True
            if isinstance(node, ast.If):
                exits = _body_has_exit(node.body) or _body_has_exit(node.orelse)
                if not exits:
                    continue
                for sub in ast.walk(node.test):
                    if _compare_is_none(sub):
                        return True
                    if isinstance(sub, (ast.Name, ast.Attribute)):
                        d = dotted_name(sub) or ""
                        tail = d.split(".")[-1].lower()
                        if any(h in tail for h in STOP_NAME_HINTS):
                            return True
    return False


def _donate_positions(call):
    """Literal donate_argnums positions from a jit-like call, or None when
    dynamic (None = treat every positional arg as donated)."""
    for kw in call.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return [v.value]
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for elt in v.elts:
                    if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                        out.append(elt.value)
                    else:
                        return None
                return out
            return None
    return "nodonate"


class _FunctionExtractor(ast.NodeVisitor):
    """Build one function's summary: lock events, queue/join sites, metric
    registrations, and the ordered donation-dataflow event list."""

    def __init__(self, mod, qual, class_name, node):
        self.mod = mod
        self.qual = qual
        self.class_name = class_name
        self.summary = {
            "line": node.lineno,
            "class": class_name,
            "acquires": [],       # [lock_id, line]
            "edges": [],          # [held_id, acquired_id, line] (nested with)
            "calls_under": [],    # [held_id, callee_ref, line]
            "calls": [],          # callee_ref strings
            "joins_under": [],    # [held_id, line, has_timeout]
            "puts_under": [],     # [held_id, queue_attr, line, blocking]
            "queue_gets": [],     # queue attr names ("C.q")
            "events": [],         # donation dataflow IR
            "metric_regs": [],    # [kind, name|None, line, recv]
            "registry_vars": [],  # [var, line]
            "registry_published": [],  # var names reaching a publish call
            "registry_escapes": [],    # var names passed/stored elsewhere
            "env_ops": [],        # [kind("read"|"write"), key, line]
            "spawns": [],         # [kind, target, daemon(1/0/-1), stored, line]
            "thread_joins": [],   # [recv, timed(1/0), line]
            "wloops": [],         # [line, has_stop(1/0)] (`while True` only)
            "fsio": [],           # [op, a, b, line] ordered commit-I/O events
        }
        self._held = []  # stack of lock ids currently held (with-blocks)
        self._var_types = {}  # local var -> ctor ref (`w = Worker()`)
        self.summary["var_types"] = self._var_types
        self._chaos_guard = 0  # >0 inside an `if chaos...` torn-write branch
        self._dirfds = set()   # locals bound from os.open(dir, O_RDONLY)
        self._var_spawn = {}   # local var -> spawn record (daemon post-sets)

    # -- lock identity -------------------------------------------------------

    def _lock_id(self, expr):
        """Resolved identity of a lock expression, or None.

        ``self.X`` resolves against the enclosing class's known lock/sync
        attributes; a bare module-level lock name resolves against the
        module summary. Unresolvable expressions don't contribute graph
        edges (under-approximation keeps the rule quiet, not noisy).
        """
        name = dotted_name(expr)
        if name is None:
            return None
        if name.startswith("self.") and self.class_name:
            attr = name[5:]
            cls = self.mod.summary["classes"].get(self.class_name, {})
            if attr in cls.get("lock_attrs", ()) or attr in cls.get("sync_attrs", ()):
                return "{}:{}.{}".format(self.mod.module, self.class_name, attr)
            if any(h in attr.lower() for h in LOCK_NAME_HINTS):
                return "{}:{}.{}".format(self.mod.module, self.class_name, attr)
            return None
        if "." not in name:
            if name in self.mod.module_locks:
                return "{}:{}".format(self.mod.module, name)
            if any(h in name.lower() for h in LOCK_NAME_HINTS):
                # local or imported lock: identity is function-scoped
                return None
            return None
        # alias.lockname through an import
        head, _, tail = name.partition(".")
        target = self.mod.imports.get(head)
        if target and any(h in tail.lower() for h in LOCK_NAME_HINTS):
            return "{}:{}".format(target, tail)
        return None

    # -- callee references ---------------------------------------------------

    def _callee_ref(self, func):
        """A reference string phase 2 can resolve: ``self.m``, ``self.a.m``,
        ``f``, ``alias.f`` — or None for dynamic callees."""
        return dotted_name(func)

    # -- statement walk ------------------------------------------------------

    def extract(self, node):
        for stmt in node.body:
            self._stmt(stmt)
        return self.summary

    def _stmt(self, stmt):
        ev = self.summary["events"]
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested defs are summarized separately by the module extractor
            return
        if isinstance(stmt, ast.With) or isinstance(stmt, ast.AsyncWith):
            self._with(stmt)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._expr_uses(stmt.iter)
            tgt = stmt.target
            if isinstance(tgt, ast.Name):
                src = self._classify(stmt.iter)
                if src[0] in ("src", "alias", "aliasany"):
                    ev.append(["asn", tgt.id, src[0], src[1], stmt.lineno])
                else:
                    ev.append(["asn", tgt.id, "clean", None, stmt.lineno])
            for s in stmt.body:
                self._stmt(s)
            for s in stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, ast.While):
            self._expr_uses(stmt.test)
            if (
                isinstance(stmt.test, ast.Constant)
                and stmt.test.value
                # generator pull-loops (`while True: yield ...`) are driven
                # by their consumer; the stop signal lives in the caller
                and not any(
                    isinstance(n, (ast.Yield, ast.YieldFrom))
                    for s in stmt.body
                    for n in ast.walk(s)
                )
            ):
                self.summary["wloops"].append(
                    [stmt.lineno, 1 if _while_true_has_stop(stmt.body) else 0]
                )
            for s in stmt.body:
                self._stmt(s)
            for s in stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, ast.If):
            self._expr_uses(stmt.test)
            chaos_branch = _is_chaos_test(stmt.test)
            if chaos_branch:
                self._chaos_guard += 1
            for s in stmt.body:
                self._stmt(s)
            if chaos_branch:
                self._chaos_guard -= 1
            for s in stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, ast.Try):
            for s in stmt.body:
                self._stmt(s)
            for h in stmt.handlers:
                for s in h.body:
                    self._stmt(s)
            for s in stmt.orelse:
                self._stmt(s)
            for s in stmt.finalbody:
                self._stmt(s)
            return
        if isinstance(stmt, ast.Return):
            if stmt.value is not None:
                self._expr_uses(stmt.value)
                kind = self._classify(stmt.value)
                if kind[0] == "alias":
                    ev.append(["ret", kind[1], stmt.lineno])
                elif kind[0] == "aliasany":
                    for v in kind[1]:
                        ev.append(["ret", v, stmt.lineno])
                elif kind[0] == "src":
                    ev.append(["retsrc", kind[1], stmt.lineno])
                elif kind[0] == "call":
                    ev.append(["retcall", kind[1][0], kind[1][1], stmt.lineno])
            return
        if isinstance(stmt, ast.Assign):
            self._assign(stmt)
            return
        if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._assign(ast.Assign(targets=[stmt.target], value=stmt.value, lineno=stmt.lineno))
            return
        if isinstance(stmt, ast.AugAssign):
            self._expr_uses(stmt.value)
            tgt = stmt.target
            base = root_name(tgt)
            if base:
                ev.append(["wsink", base, stmt.lineno, "augmented assignment mutates the buffer in place"])
            return
        if isinstance(stmt, ast.Expr):
            self._expr_stmt(stmt.value)
            return
        if isinstance(stmt, (ast.Assert, ast.Raise, ast.Delete)):
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.expr):
                    self._expr_uses(child)
            return
        # default: record any uses/calls inside
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                self._expr_uses(child)
            elif isinstance(child, ast.stmt):
                self._stmt(child)

    def _with(self, stmt):
        acquired_here = 0
        for item in stmt.items:
            cm = item.context_expr
            self._expr_uses(cm)
            lock_expr = None
            if isinstance(cm, ast.Call):
                name = dotted_name(cm.func)
                if name and name.split(".")[-1] in ("acquire",):
                    lock_expr = cm.func.value
            else:
                lock_expr = cm
            if lock_expr is None:
                continue
            lid = self._lock_id(lock_expr)
            if lid is None:
                continue
            self.summary["acquires"].append([lid, stmt.lineno])
            for held in self._held:
                self.summary["edges"].append([held, lid, stmt.lineno])
            self._held.append(lid)
            acquired_here += 1
        for s in stmt.body:
            self._stmt(s)
        for _ in range(acquired_here):
            self._held.pop()

    def _assign(self, stmt):
        ev = self.summary["events"]
        self._expr_uses(stmt.value)
        value = stmt.value
        kind = self._classify(value)
        # spawn storage: `self.t = Thread(...)` / `t = Thread(...)` marks
        # the spawn record so join discipline knows where the handle lives
        if isinstance(value, ast.Call) and self.summary["spawns"]:
            ctor = dotted_name(value.func) or ""
            if ctor.split(".")[-1] in SPAWN_CTORS:
                rec = self.summary["spawns"][-1]
                if rec[4] == value.lineno and not rec[3]:
                    tgt0 = stmt.targets[0]
                    tname = dotted_name(tgt0)
                    if tname and tname.startswith("self.") and tname.count(".") == 1:
                        rec[3] = tname
                    elif isinstance(tgt0, ast.Name):
                        rec[3] = "var:" + tgt0.id
                        self._var_spawn[tgt0.id] = rec
        # pooling sinks: storing into an attribute or attribute-subscript
        for tgt in stmt.targets:
            if isinstance(tgt, ast.Attribute):
                # `t.daemon = True` after the ctor amends the spawn record
                if tgt.attr == "daemon":
                    base = root_name(tgt)
                    rec = self._var_spawn.get(base) if base else None
                    if rec is not None and isinstance(value, ast.Constant):
                        rec[2] = 1 if value.value else 0
                tname = dotted_name(tgt) or tgt.attr
                for v in self._value_vars(kind):
                    ev.append(["psink", v, stmt.lineno,
                               "stored into attribute `{}` (outlives the call)".format(tname)])
                for v in self._value_vars(kind):
                    if v not in self.summary["registry_escapes"]:
                        self.summary["registry_escapes"].append(v)
            elif isinstance(tgt, ast.Subscript):
                self._env_subscript(tgt, "write")
                base = root_name(tgt)
                if isinstance(tgt.value, ast.Attribute):
                    tname = dotted_name(tgt.value) or "container"
                    for v in self._value_vars(kind):
                        ev.append(["psink", v, stmt.lineno,
                                   "stored into `{}[...]` (outlives the call)".format(tname)])
                elif base:
                    ev.append(["wsink", base, stmt.lineno,
                               "subscript store writes into the buffer in place"])
            elif isinstance(tgt, ast.Name):
                self._bind(tgt.id, value, kind, stmt.lineno)
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                for elt in tgt.elts:
                    if isinstance(elt, ast.Name):
                        ev.append(["asn", elt.id, "clean", None, stmt.lineno])

    def _bind(self, name, value, kind, lineno):
        ev = self.summary["events"]
        # local instance types for callee resolution (`w = Worker()`)
        if isinstance(value, ast.Call):
            ctor = dotted_name(value.func)
            if ctor:
                self._var_types[name] = ctor
                tail = ctor.split(".")[-1]
                if tail == "Registry":
                    self.summary["registry_vars"].append([name, lineno])
                if ctor == "os.open" and (
                    any(
                        isinstance(n, ast.Attribute) and n.attr == "O_DIRECTORY"
                        for a in value.args
                        for n in ast.walk(a)
                    )
                    or (
                        any(
                            isinstance(n, ast.Attribute) and n.attr == "O_RDONLY"
                            for a in value.args
                            for n in ast.walk(a)
                        )
                        and (
                            "dir" in name.lower()
                            or (value.args and _name_has_dir_hint(value.args[0]))
                        )
                    )
                ):
                    # `dirfd = os.open(dirpath, os.O_RDONLY)`: fsync(dirfd)
                    # below is a directory-entry fsync, not a data-file fsync
                    self._dirfds.add(name)
        if kind[0] == "jitdon":
            ev.append(["jitdon", name, kind[1], lineno])
            return
        if kind[0] in ("src", "alias", "clean"):
            ev.append(["asn", name, kind[0], kind[1], lineno])
        elif kind[0] == "aliasany":
            ev.append(["asn", name, "aliasany", kind[1], lineno])
        elif kind[0] == "call":
            ev.append(["asn", name, "call", kind[1], lineno])
        else:
            ev.append(["asn", name, "clean", None, lineno])

    def _value_vars(self, kind):
        if kind[0] == "alias":
            return [kind[1]]
        if kind[0] == "aliasany":
            return list(kind[1])
        return []

    def _classify(self, value):
        """Taint classification of an assigned/returned expression."""
        if isinstance(value, ast.Call):
            name = dotted_name(value.func) or ""
            tail = name.split(".")[-1]
            if tail == "device_get":
                return ("src", "jax.device_get")
            if tail == "asarray":
                # asarray PROPAGATES taint; it never introduces it
                if value.args:
                    inner = self._classify(value.args[0])
                    if inner[0] in ("src", "alias", "aliasany"):
                        return inner
                return ("clean", None)
            if tail in CLEANING_CALLS:
                return ("clean", None)
            if tail in ("jit", "pjit") or name.endswith("compile_train_loop"):
                pos = _donate_positions(value)
                if pos == "nodonate":
                    # compile_train_loop(donate=True) donates the state
                    # (positional arg 0 of the compiled callable)
                    for kw in value.keywords:
                        if kw.arg == "donate" and not (
                            isinstance(kw.value, ast.Constant) and not kw.value.value
                        ):
                            return ("jitdon", [0])
                    return ("clean", None)
                return ("jitdon", pos)
            argvars = [a.id if isinstance(a, ast.Name) else None for a in value.args]
            return ("call", [name, argvars])
        if isinstance(value, ast.Attribute):
            if value.attr == "addressable_shards":
                return ("src", ".addressable_shards")
            base = root_name(value)
            if base:
                return ("alias", base)
            return ("clean", None)
        if isinstance(value, ast.Subscript):
            base = root_name(value)
            return ("alias", base) if base else ("clean", None)
        if isinstance(value, ast.Name):
            return ("alias", value.id)
        if isinstance(value, (ast.ListComp, ast.GeneratorExp)):
            elt = value.elt
            inner = self._classify(elt)
            if inner[0] in ("src", "call"):
                return inner
            if inner[0] == "alias":
                # comprehension over locals: taint if the element is tainted
                return ("alias", inner[1])
            return ("clean", None)
        if isinstance(value, (ast.List, ast.Tuple)):
            names = [e.id for e in value.elts if isinstance(e, ast.Name)]
            if names:
                return ("aliasany", names)
            return ("clean", None)
        return ("clean", None)

    def _expr_stmt(self, value):
        """An expression statement — usually a call with side effects."""
        ev = self.summary["events"]
        self._expr_uses(value)
        if not isinstance(value, ast.Call):
            return
        name = dotted_name(value.func) or ""
        tail = name.split(".")[-1]
        # np.copyto(dst, src): writes into dst
        if tail == "copyto" and value.args and isinstance(value.args[0], ast.Name):
            ev.append(["wsink", value.args[0].id, value.lineno,
                       "np.copyto writes into the destination buffer in place"])
        # receiver method calls
        if isinstance(value.func, ast.Attribute):
            recv = value.func.value
            if tail in INPLACE_METHODS and isinstance(recv, ast.Name):
                ev.append(["wsink", recv.id, value.lineno,
                           "`.{}()` mutates the buffer in place".format(tail)])
            if tail in POOL_METHODS and isinstance(recv, (ast.Attribute, ast.Subscript)):
                rname = dotted_name(recv) or "container"
                for a in value.args:
                    if isinstance(a, ast.Name):
                        ev.append(["psink", a.id, value.lineno,
                                   "appended to `{}` (outlives the call)".format(rname)])

    def _queue_op(self, call, tail, held):
        qname = dotted_name(call.func.value)
        if not (qname and qname.startswith("self.") and self.class_name):
            return
        attr = qname[5:]
        cls = self.mod.summary["classes"].get(self.class_name, {})
        if attr not in cls.get("queue_attrs", {}):
            return
        ref = "{}.{}".format(self.class_name, attr)
        if tail.startswith("get"):
            if ref not in self.summary["queue_gets"]:
                self.summary["queue_gets"].append(ref)
            return
        blocking = tail == "put"
        if blocking:
            for kw in call.keywords:
                if kw.arg == "timeout" and not (
                    isinstance(kw.value, ast.Constant) and kw.value.value is None
                ):
                    blocking = False
                if kw.arg == "block" and isinstance(kw.value, ast.Constant) and not kw.value.value:
                    blocking = False
        if held is not None:
            self.summary["puts_under"].append([held, ref, call.lineno, blocking])

    def _env_subscript(self, node, kind):
        """``<recv>[KEY]`` access where either the receiver is ``environ``
        or the key is a literal on a checked env lane."""
        recv = dotted_name(node.value) or ""
        key = _env_key(node.slice)
        if key is None:
            return
        is_environ = recv == "environ" or recv.endswith(".environ")
        if is_environ or (not key.startswith("$") and _is_env_lane_literal(key)):
            self.summary["env_ops"].append([kind, key, node.lineno])

    def _env_op(self, kind, key, line):
        if key is not None:
            self.summary["env_ops"].append([kind, key, line])

    def _fsio(self, op, a, b, line):
        if self._chaos_guard == 0:
            self.summary["fsio"].append([op, a or "", b or "", line])

    def _expr_uses(self, expr):
        """Record name uses, calls, metric registrations and sanitizers
        anywhere inside an expression (in source order)."""
        ev = self.summary["events"]
        for node in ast.walk(expr):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                ev.append(["use", node.id, node.lineno])
            elif isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
                self._env_subscript(node, "read")
            elif isinstance(node, ast.Dict):
                # env dict literals handed to a spawn/propagation path
                # (`child_env = {TRACE_ENV: tid, ...}`) are lane producers
                for k in node.keys:
                    if k is None:
                        continue
                    key = _env_key(k)
                    if key is not None and (
                        key.startswith("$") or _is_env_lane_literal(key)
                    ):
                        self._env_op("write", key, k.lineno)
            elif (
                isinstance(node, ast.Attribute)
                and node.attr == "writeable"
                and isinstance(node.value, ast.Attribute)
                and node.value.attr == "flags"
            ):
                # only a writability check proves the caller handles the
                # read-only-view case — .flags.owndata alone was exactly the
                # PR 7 bug (jax's cached assembly owns its data, frozen)
                base = root_name(node)
                if base:
                    ev.append(["san", base, node.lineno])
            elif isinstance(node, ast.Call):
                self._record_call(node)

    def _record_call(self, call):
        name = dotted_name(call.func)
        if not name:
            return
        if name not in self.summary["calls"]:
            self.summary["calls"].append(name)
        held = self._held[-1] if self._held else None
        if held is not None:
            self.summary["calls_under"].append([held, name, call.lineno])
        tail = name.split(".")[-1]
        argvars = [a.id if isinstance(a, ast.Name) else None for a in call.args]
        # donation interpreter input: every call site with positional names.
        # The line is the call's END line so arg reads inside a multi-line
        # donating call don't count as reads-after-donation.
        self.summary["events"].append(
            ["call", name, argvars, getattr(call, "end_lineno", None) or call.lineno]
        )
        if name.startswith("self.") and isinstance(call.func, ast.Attribute):
            if tail == "join" and not call.args:
                has_timeout = any(kw.arg == "timeout" for kw in call.keywords)
                if held is not None:
                    self.summary["joins_under"].append([held, call.lineno, has_timeout])
            if tail in ("put", "put_nowait", "get", "get_nowait"):
                self._queue_op(call, tail, held)
        self._lifecycle_call(call, name, tail)
        self._env_call(call, name, tail)
        self._fsio_call(call, name, tail)
        # metric registrations: <recv>.counter("name", ...)
        if tail in ("counter", "gauge", "histogram") and isinstance(call.func, ast.Attribute):
            recv = dotted_name(call.func.value)
            if recv is not None:
                lit = _literal_str(call.args[0]) if call.args else None
                self.summary["metric_regs"].append(
                    [tail, lit, call.lineno, self._recv_kind(recv)]
                )
        if tail in PUBLISH_CALLS:
            for a in list(call.args) + [kw.value for kw in call.keywords]:
                if isinstance(a, ast.Name):
                    if a.id not in self.summary["registry_published"]:
                        self.summary["registry_published"].append(a.id)
        else:
            # a registry var passed to any other call escapes the function
            for a in list(call.args) + [kw.value for kw in call.keywords]:
                if isinstance(a, ast.Name):
                    if a.id not in self.summary["registry_escapes"]:
                        self.summary["registry_escapes"].append(a.id)

    def _lifecycle_call(self, call, name, tail):
        """Thread spawns and thread joins (thread-lifecycle facts)."""
        if tail in SPAWN_CTORS or tail == "submit":
            kind = {"Thread": "thread", "Timer": "timer"}.get(tail, "submit")
            cand = None
            if kind == "submit" and call.args:
                cand = call.args[0]
            elif kind == "timer" and len(call.args) > 1:
                cand = call.args[1]
            for kw in call.keywords:
                if kw.arg in ("target", "function"):
                    cand = kw.value
            target = dotted_name(cand) if cand is not None else None
            daemon = -1
            for kw in call.keywords:
                if kw.arg == "daemon" and isinstance(kw.value, ast.Constant):
                    daemon = 1 if kw.value.value else 0
            self.summary["spawns"].append(
                [kind, target or "", daemon, "", call.lineno]
            )
        elif (
            tail == "join"
            and isinstance(call.func, ast.Attribute)
            and all(kw.arg == "timeout" for kw in call.keywords)
            and (
                not call.args
                or (
                    len(call.args) == 1
                    and isinstance(call.args[0], ast.Constant)
                    and isinstance(call.args[0].value, (int, float))
                )
            )
        ):
            recv = dotted_name(call.func.value)
            if recv is not None:
                timed = bool(call.args) or any(
                    kw.arg == "timeout"
                    and not (
                        isinstance(kw.value, ast.Constant) and kw.value.value is None
                    )
                    for kw in call.keywords
                )
                self.summary["thread_joins"].append(
                    [recv, 1 if timed else 0, call.lineno]
                )

    def _env_call(self, call, name, tail):
        """Env-lane reads/writes through call syntax."""
        recv = (
            dotted_name(call.func.value)
            if isinstance(call.func, ast.Attribute)
            else None
        ) or ""
        is_environ = recv == "environ" or recv.endswith(".environ")
        key = _env_key(call.args[0]) if call.args else None
        if name == "os.getenv" or (tail == "getenv" and not recv):
            self._env_op("read", key, call.lineno)
        elif tail == "get" and key is not None:
            # environ.get always counts; `.get` on any other receiver only
            # for lane-shaped keys (env dicts handed between processes)
            if is_environ or key.startswith("$") or _is_env_lane_literal(key):
                self._env_op("read", key, call.lineno)
        elif tail == "setdefault" and key is not None:
            if is_environ or (not key.startswith("$") and _is_env_lane_literal(key)):
                self._env_op("write", key, call.lineno)

    def _fsio_call(self, call, name, tail):
        """Ordered commit-I/O events (commit-discipline facts)."""
        if name == "os.fsync":
            arg = call.args[0] if call.args else None
            if isinstance(arg, ast.Name) and arg.id in self._dirfds:
                self._fsio("fsyncd", "", "", call.lineno)
            else:
                self._fsio("fsyncf", "", "", call.lineno)
        elif "fsync_dir" in tail or tail == "dirsync":
            self._fsio("fsyncd", "", "", call.lineno)
        elif name in ("os.rename", "os.replace") and len(call.args) >= 2:
            src, dst = call.args[0], call.args[1]
            self._fsio(
                "rename",
                dotted_name(src) or ("tmp" if _name_has_tmp_hint(src) else ""),
                dotted_name(dst) or "",
                call.lineno,
            )
        elif tail == "write_manifest":
            self._fsio("manifest", "", "", call.lineno)
        elif tail == "verify":
            self._fsio("verify", "", "", call.lineno)
        elif name == "open" and len(call.args) >= 2:
            mode = _literal_str(call.args[1])
            if mode and ("w" in mode or "x" in mode):
                hint = 1 if _name_has_tmp_hint(call.args[0]) else 0
                self._fsio("openw", str(hint), "", call.lineno)
        elif tail in ("NamedTemporaryFile", "mkstemp"):
            self._fsio("openw", "1", "", call.lineno)

    def _recv_kind(self, recv):
        """'global' when the receiver is the shared obs registry module,
        'var:<name>' for a local Registry() instance, 'other' otherwise."""
        head = recv.split(".")[0]
        if head == "self" and self.mod.relpath.replace("\\", "/").endswith(
            "obs/registry.py"
        ):
            # Registry methods registering on themselves ARE the global
            # registry's own bookkeeping (e.g. obs_events_dropped_total).
            return "global"
        target = self.mod.imports.get(head, "")
        if target == "tensorflowonspark_tpu.obs" or target.startswith(
            "tensorflowonspark_tpu.obs."
        ) or head == "obs":
            return "global"
        if "." not in recv and any(recv == v for v, _ in self.summary["registry_vars"]):
            return "var:" + recv
        return "other"


class _ModuleExtractor:
    """Walk one module tree and produce its summary dict."""

    def __init__(self, tree, source, relpath):
        self.tree = tree
        self.source = source
        self.relpath = relpath
        self.module = module_name(relpath)
        self.imports = {}
        self.module_locks = set()
        self.summary = {
            "module": self.module,
            "imports": self.imports,
            "classes": {},
            "functions": {},
            "chaos": None,
            "trace": None,
        }

    def extract(self):
        self._imports()
        self._module_level()
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._function(node, None)
            elif isinstance(node, ast.ClassDef):
                self._class(node)
        self._chaos_facts()
        self._trace_facts()
        return self.summary

    def _imports(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.imports[a.asname or a.name] = "{}.{}".format(node.module, a.name)

    def _module_level(self):
        donators = {}
        consts = {}
        for node in self.tree.body:
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
            ):
                cname = node.targets[0].id
                lit = _literal_str(node.value)
                if lit is not None:
                    consts[cname] = ["lit", lit]
                else:
                    ref = dotted_name(node.value)
                    if ref:
                        consts[cname] = ["ref", ref]
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                ctor = dotted_name(node.value.func) or ""
                tail = ctor.split(".")[-1]
                if tail in LOCK_CTORS:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            self.module_locks.add(tgt.id)
                if tail in ("jit", "pjit") or ctor.endswith("compile_train_loop"):
                    pos = _donate_positions(node.value)
                    if pos != "nodonate":
                        for tgt in node.targets:
                            if isinstance(tgt, ast.Name):
                                donators[tgt.id] = pos
        self.summary["module_locks"] = sorted(self.module_locks)
        self.summary["jit_donators"] = donators
        self.summary["consts"] = consts
        self.summary["env_ops"] = self._module_env_ops()

    def _module_env_ops(self):
        """Env-lane reads/writes in module-level code (``HEARTBEAT_INTERVAL
        = float(os.environ.get(...))``) — the function extractor never sees
        these, and a lane whose only consumer is an import-time default
        would otherwise look like an orphan producer."""
        ops = []
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Subscript):
                    recv = dotted_name(sub.value) or ""
                    key = _env_key(sub.slice)
                    if key is None:
                        continue
                    if recv == "environ" or recv.endswith(".environ") or (
                        not key.startswith("$") and _is_env_lane_literal(key)
                    ):
                        kind = "write" if isinstance(sub.ctx, (ast.Store, ast.Del)) else "read"
                        ops.append([kind, key, sub.lineno])
                elif isinstance(sub, ast.Call):
                    name = dotted_name(sub.func) or ""
                    tail = name.split(".")[-1]
                    recv = (
                        dotted_name(sub.func.value)
                        if isinstance(sub.func, ast.Attribute)
                        else None
                    ) or ""
                    is_environ = recv == "environ" or recv.endswith(".environ")
                    key = _env_key(sub.args[0]) if sub.args else None
                    if key is None:
                        continue
                    if name == "os.getenv" or (tail == "getenv" and not recv):
                        ops.append(["read", key, sub.lineno])
                    elif tail == "get" and (
                        is_environ or key.startswith("$") or _is_env_lane_literal(key)
                    ):
                        ops.append(["read", key, sub.lineno])
                    elif tail == "setdefault" and (
                        is_environ or (not key.startswith("$") and _is_env_lane_literal(key))
                    ):
                        ops.append(["write", key, sub.lineno])
        return ops

    def _class(self, node):
        cls = {
            "lock_attrs": [],
            "sync_attrs": [],
            "queue_attrs": {},
            "spawn_targets": [],
            "attr_types": {},
            "methods": [],
        }
        self.summary["classes"][node.name] = cls
        methods = [
            n for n in node.body if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        cls["methods"] = [m.name for m in methods]
        # first pass over method bodies: attribute classification
        for m in methods:
            for sub in ast.walk(m):
                if isinstance(sub, ast.Assign) and isinstance(sub.value, ast.Call):
                    ctor = dotted_name(sub.value.func) or ""
                    tail = ctor.split(".")[-1]
                    for tgt in sub.targets:
                        tname = dotted_name(tgt)
                        if not (tname and tname.startswith("self.") and tname.count(".") == 1):
                            continue
                        attr = tname[5:]
                        if tail in ("Lock", "RLock"):
                            if attr not in cls["lock_attrs"]:
                                cls["lock_attrs"].append(attr)
                        elif tail in LOCK_CTORS:
                            if attr not in cls["sync_attrs"]:
                                cls["sync_attrs"].append(attr)
                        elif tail in QUEUE_CTORS:
                            bounded = tail != "SimpleQueue" and self._queue_bounded(sub.value)
                            cls["queue_attrs"][attr] = {
                                "bounded": bounded,
                                "line": sub.lineno,
                                "mod": self._ctor_module(ctor),
                            }
                        elif ctor:
                            cls["attr_types"][attr] = ctor
                elif isinstance(sub, ast.Call):
                    ctor = dotted_name(sub.func) or ""
                    tail = ctor.split(".")[-1]
                    if tail in SPAWN_CTORS or tail == "submit":
                        tgt = self._spawn_target(sub, tail)
                        if tgt and tgt not in cls["spawn_targets"]:
                            cls["spawn_targets"].append(tgt)
        for m in methods:
            self._function(m, node.name)

    def _ctor_module(self, ctor):
        """Defining module of a ctor ref, resolved through imports
        (``queue_mod.Queue`` → ``queue``; bare ``Queue`` from-import →
        ``queue``); the raw head when unresolvable (``_mp.Queue``)."""
        if "." in ctor:
            head = ctor.split(".", 1)[0]
            return self.imports.get(head, head)
        target = self.imports.get(ctor, "")
        return target.rsplit(".", 1)[0] if "." in target else ""

    def _queue_bounded(self, call):
        if call.args:
            a = call.args[0]
            return not (isinstance(a, ast.Constant) and a.value in (0, None))
        for kw in call.keywords:
            if kw.arg == "maxsize":
                return not (isinstance(kw.value, ast.Constant) and kw.value.value in (0, None))
        return False

    def _spawn_target(self, call, tail):
        """`self.X` method name handed to Thread(target=...)/submit(...)."""
        cand = None
        if tail == "submit" and call.args:
            cand = call.args[0]
        for kw in call.keywords:
            if kw.arg == "target":
                cand = kw.value
        name = dotted_name(cand) if cand is not None else None
        if name and name.startswith("self.") and name.count(".") == 1:
            return name[5:]
        return None

    def _function(self, node, class_name):
        qual = "{}.{}".format(class_name, node.name) if class_name else node.name
        fx = _FunctionExtractor(self, qual, class_name, node)
        self.summary["functions"][qual] = fx.extract(node)
        for sub in node.body:
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested = "{}.<{}>".format(qual, sub.name)
                nfx = _FunctionExtractor(self, nested, class_name, sub)
                self.summary["functions"][nested] = nfx.extract(sub)

    def _chaos_facts(self):
        """Fired chaos sites (and, for the chaos module itself, the
        docstring site table) — the cross-file half of chaos-obs-coverage
        so the rule still runs when per-file walks are cache hits."""
        is_chaos = self.relpath.replace("\\", "/").endswith("chaos/__init__.py")
        fires = []
        if not is_chaos:
            for node in ast.walk(self.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                parts = name.split(".")
                if len(parts) == 2 and parts[0] == "chaos" and parts[1] in ("fire", "delay"):
                    lit = _literal_str(node.args[0]) if node.args else None
                    if lit is not None:
                        fires.append([lit, node.lineno])
        facts = {"fires": fires}
        if is_chaos:
            from .checkers.chaos_obs import COUNTER_NAME, SITE_LINE_RE

            doc = ast.get_docstring(self.tree) or ""
            facts["table"] = [
                m.group("site")
                for m in (SITE_LINE_RE.match(line) for line in doc.splitlines())
                if m
            ]
            facts["doc_line"] = self.tree.body[0].lineno if self.tree.body else 1
            facts["counter_in_source"] = COUNTER_NAME in self.source
        self.summary["chaos"] = facts

    def _trace_facts(self):
        """Literal span sites (and, for obs/tracing.py, the docstring
        span-site table) — the cross-file half of trace-discipline so the
        rule still runs when per-file walks are cache hits."""
        from .checkers.trace_discipline import (
            SITE_LINE_RE,
            SPAN_FUNCS,
            TRACE_RECEIVERS,
            _in_obs_package,
            _is_tracing_module,
        )

        fires = []
        if not _in_obs_package(self.relpath):
            for node in ast.walk(self.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                parts = name.split(".")
                if (
                    len(parts) == 2
                    and parts[0] in TRACE_RECEIVERS
                    and parts[1] in SPAN_FUNCS
                ):
                    lit = _literal_str(node.args[0]) if node.args else None
                    if lit is not None:
                        fires.append([lit, node.lineno])
        facts = {"fires": fires}
        if _is_tracing_module(self.relpath):
            doc = ast.get_docstring(self.tree) or ""
            facts["table"] = [
                m.group("site")
                for m in (SITE_LINE_RE.match(line) for line in doc.splitlines())
                if m
            ]
            facts["doc_line"] = self.tree.body[0].lineno if self.tree.body else 1
        self.summary["trace"] = facts


def summarize(tree, source, relpath):
    """One-pass module summary (JSON-serializable dict)."""
    return _ModuleExtractor(tree, source, relpath).extract()


class ProjectIndex:
    """Phase-1 output: per-module summaries plus docs text, with resolution
    helpers shared by the phase-2 checkers."""

    def __init__(self, root=None, docs=None):
        self.root = root
        self.modules = {}  # relpath -> summary dict
        self.docs = docs or {}  # relpath -> text (docs/architecture.md)
        self._by_name = {}

    def add_summary(self, relpath, summary):
        if summary is None:
            return
        self.modules[relpath] = summary
        self._by_name[summary["module"]] = relpath

    def load_docs(self, relpaths=("docs/architecture.md",)):
        if self.root is None:
            return
        for rel in relpaths:
            path = os.path.join(self.root, rel)
            if os.path.isfile(path):
                with open(path, encoding="utf-8") as f:
                    self.docs[rel] = f.read()

    def module_path(self, dotted):
        """relpath for a dotted module name (also tries package __init__)."""
        return self._by_name.get(dotted)

    # -- call resolution -----------------------------------------------------

    def resolve_call(self, relpath, class_name, callee_ref, local_types=None):
        """(relpath, qual) of the target function, or None."""
        mod = self.modules.get(relpath)
        if mod is None or not callee_ref:
            return None
        if callee_ref.startswith("self.") and class_name:
            rest = callee_ref[5:]
            cls = mod["classes"].get(class_name, {})
            if "." not in rest:
                if rest in cls.get("methods", ()):
                    return (relpath, "{}.{}".format(class_name, rest))
                return None
            attr, _, meth = rest.partition(".")
            ctor = cls.get("attr_types", {}).get(attr)
            if ctor:
                return self._resolve_ctor_method(relpath, mod, ctor, meth)
            return None
        if "." not in callee_ref:
            if callee_ref in mod["functions"]:
                return (relpath, callee_ref)
            target = mod["imports"].get(callee_ref)
            if target:
                return self._resolve_dotted(target)
            return None
        head, _, tail = callee_ref.partition(".")
        if local_types and head in local_types:
            return self._resolve_ctor_method(relpath, mod, local_types[head], tail)
        if head in mod["classes"]:
            qual = "{}.{}".format(head, tail)
            if qual in mod["functions"]:
                return (relpath, qual)
            return None
        target = mod["imports"].get(head)
        if target:
            return self._resolve_dotted("{}.{}".format(target, tail))
        return None

    def _resolve_ctor_method(self, relpath, mod, ctor, meth):
        """Resolve ``K.meth`` where K is a class ref seen at a ctor site."""
        head = ctor.split(".")[0]
        cls_name = ctor.split(".")[-1]
        if head in mod["imports"]:
            dotted = mod["imports"][head]
            if "." in ctor:
                dotted = "{}.{}".format(mod["imports"][head], cls_name)
            target_rel = self._class_module(dotted, cls_name)
        else:
            target_rel = relpath if cls_name in mod["classes"] else self._class_module(ctor, cls_name)
        if target_rel is None:
            return None
        qual = "{}.{}".format(cls_name, meth)
        if qual in self.modules[target_rel]["functions"]:
            return (target_rel, qual)
        return None

    def _class_module(self, dotted, cls_name):
        """relpath of the module defining ``cls_name`` given a dotted ref."""
        # dotted may be module.Class or package.module; try both splits
        if "." in dotted:
            mod_part = dotted.rsplit(".", 1)[0]
            rel = self._by_name.get(mod_part)
            if rel and cls_name in self.modules[rel]["classes"]:
                return rel
        rel = self._by_name.get(dotted)
        if rel and cls_name in self.modules[rel]["classes"]:
            return rel
        return None

    def _resolve_dotted(self, dotted):
        """module.func (or package.module.func) -> (relpath, qual)."""
        if "." not in dotted:
            return None
        mod_part, func = dotted.rsplit(".", 1)
        rel = self._by_name.get(mod_part)
        if rel and func in self.modules[rel]["functions"]:
            return (rel, func)
        return None

    # -- iteration helpers ---------------------------------------------------

    def functions(self):
        """Yield (relpath, qual, function summary) across the project."""
        for relpath in sorted(self.modules):
            mod = self.modules[relpath]
            for qual in sorted(mod["functions"]):
                yield relpath, qual, mod["functions"][qual]


# -- cache -------------------------------------------------------------------

CACHE_VERSION = 4


def _tool_signature():
    """Fingerprint of the analyzer's own sources: any checker edit
    invalidates the cache (stale summaries must never hide findings)."""
    pkg = os.path.dirname(os.path.abspath(__file__))
    parts = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if name.endswith(".py"):
                p = os.path.join(dirpath, name)
                st = os.stat(p)
                parts.append("{}:{}:{}".format(name, st.st_size, st.st_mtime_ns))
    return hashlib.md5("|".join(parts).encode()).hexdigest()


def content_hash(data):
    return hashlib.md5(data).hexdigest()


class IndexCache:
    """Content-hash keyed store of per-file summaries + walk findings."""

    def __init__(self, path, rules):
        self.path = path
        self.rules = sorted(rules)
        self.files = {}
        self.dirty = False

    def get(self, relpath, digest):
        entry = self.files.get(relpath)
        if entry and entry.get("hash") == digest:
            return entry
        return None

    def put(self, relpath, digest, summary, findings, suppressions):
        self.files[relpath] = {
            "hash": digest,
            "summary": summary,
            "findings": findings,
            "suppressions": suppressions,
        }
        self.dirty = True

    def save(self):
        if not self.dirty:
            return
        payload = {
            "cache_version": CACHE_VERSION,
            "toolsig": _tool_signature(),
            "rules": self.rules,
            "files": self.files,
        }
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f, separators=(",", ":"), sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass  # a cold cache next run is the only consequence


def load_cache(path, rules):
    """An :class:`IndexCache`, warm when the on-disk payload matches the
    current analyzer version/ruleset, empty otherwise."""
    cache = IndexCache(path, rules)
    try:
        with open(path, encoding="utf-8") as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return cache
    if (
        payload.get("cache_version") == CACHE_VERSION
        and payload.get("toolsig") == _tool_signature()
        and payload.get("rules") == cache.rules
    ):
        cache.files = payload.get("files", {})
    return cache


def build_index(paths, root=None, cache_path=None, docs=True):
    """Build (or warm-load) the phase-1 index over ``paths``."""
    root = root or os.getcwd()
    cache = load_cache(cache_path, []) if cache_path else None
    proj = ProjectIndex(root=root)
    for path in paths:
        relpath = os.path.relpath(path, root).replace(os.sep, "/")
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            continue
        digest = content_hash(data)
        if cache is not None:
            entry = cache.get(relpath, digest)
            if entry is not None:
                proj.add_summary(relpath, entry["summary"])
                continue
        try:
            source = data.decode("utf-8")
            tree = ast.parse(source, filename=relpath)
        except (SyntaxError, UnicodeDecodeError):
            continue
        summary = summarize(tree, source, relpath)
        proj.add_summary(relpath, summary)
        if cache is not None:
            cache.put(relpath, digest, summary, [], {})
    if docs:
        proj.load_docs()
    if cache is not None:
        cache.save()
    return proj
