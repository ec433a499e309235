"""trace-discipline: span sites stay literal, scoped, and documented.

The tracing plane (PR 15) mirrors chaos-obs-coverage's contract: the
span-site table in ``obs/tracing.py``'s module docstring is what an
operator reads when filtering a merged timeline, so it must never drift
from the code.  Three invariants:

1. Every ``obs.span("name")`` / ``tracing.record_span("name", ...)``
   call uses a **literal** span name — computed names can't be listed in
   the site table or grepped for in a Perfetto trace.
2. ``span()`` is opened directly as a ``with`` context manager.  A span
   held in a variable and entered by hand can leak past an exception,
   leaving the thread-local parent stack corrupted for every later span
   on that thread.  :func:`record_span` is exempt — it is retroactive by
   design (explicit ``ts``/``dur_s``, never enters the stack).  Its caller
   in the package is ``util``'s compile listener, which JAX tells of a
   stage when the stage is over: three literal calls (``compile_trace``,
   ``compile_lower``, ``compile_backend``), which check 1 and check 3 hold
   to the table like any other site.
3. Every literal span name fired in the tree appears in the "Span sites"
   table of ``obs/tracing.py``'s docstring, and every documented site is
   fired somewhere — drift in either direction is a bug.

Checks 1 and 2 are per-file; check 3 is cross-file and is skipped when
``obs/tracing.py`` is not part of the scanned set (fixture runs).  The
``obs`` package's own internals are exempt throughout (the ``span()``
factory passes names through as variables by design).
"""

import ast
import re

from .. import core

#: single-segment receivers a span call may be spelled through
TRACE_RECEIVERS = ("obs", "trace", "tracing", "obs_trace", "obs_tracing")
SPAN_FUNCS = ("span", "record_span")
#: a span-site table row: ``site``  description  (same shape as chaos)
SITE_LINE_RE = re.compile(r"^\s*``(?P<site>[A-Za-z0-9_.]+)``\s{2,}\S")
TRACING_RELPATH_SUFFIX = "obs/tracing.py"


def _is_tracing_module(relpath):
    return relpath.replace("\\", "/").endswith(TRACING_RELPATH_SUFFIX)


def _in_obs_package(relpath):
    return "/obs/" in "/" + relpath.replace("\\", "/")


class TraceDisciplineChecker(core.Checker):
    rule = "trace-discipline"
    description = (
        "span names must be literal, spans opened via with, and the "
        "obs/tracing.py span-site table free of drift"
    )
    interests = (ast.Call,)

    def __init__(self):
        self._fired = {}          # site -> (relpath, lineno) first occurrence
        self._table = None        # None until obs/tracing.py is scanned
        self._table_anchor = None  # (relpath, lineno) of the docstring
        self._with_ids = set()    # id() of withitem context expressions

    def begin_file(self, ctx):
        self._with_ids = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    self._with_ids.add(id(item.context_expr))
        if _is_tracing_module(ctx.relpath):
            self._scan_tracing_module(ctx)

    def _scan_tracing_module(self, ctx):
        doc = ast.get_docstring(ctx.tree) or ""
        self._table = {}
        anchor_line = ctx.tree.body[0].lineno if ctx.tree.body else 1
        self._table_anchor = (ctx.relpath, anchor_line)
        for line in doc.splitlines():
            m = SITE_LINE_RE.match(line)
            if m:
                self._table[m.group("site")] = line.strip()

    def visit(self, node, ctx):
        callee = core.dotted_name(node.func)
        if callee is None:
            return
        parts = callee.split(".")
        if not (
            len(parts) == 2
            and parts[0] in TRACE_RECEIVERS
            and parts[1] in SPAN_FUNCS
        ):
            return
        if _in_obs_package(ctx.relpath):
            return  # the implementation's own internals
        func = parts[1]
        if not node.args:
            return
        name_arg = node.args[0]
        if not (isinstance(name_arg, ast.Constant) and isinstance(name_arg.value, str)):
            ctx.report(
                self,
                node,
                "{}() called with a non-literal span name — names must be "
                "string literals so the span-site table in obs/tracing.py "
                "stays auditable".format(callee),
            )
            return
        if func == "span" and id(node) not in self._with_ids:
            ctx.report(
                self,
                node,
                "span {!r} is not opened directly as a `with` context "
                "manager — a hand-entered span can leak past an exception "
                "and corrupt the thread-local parent stack (retroactive "
                "spans belong in record_span)".format(name_arg.value),
            )
        self._fired.setdefault(name_arg.value, (ctx.relpath, node.lineno))

    def check_project(self, index, run):
        """Index-driven variant of :meth:`end_run`: reads trace facts from
        the phase-1 summaries so table drift is still detected when
        per-file walks were skipped (index cache hits)."""
        table = anchor = None
        fired = {}
        for relpath in sorted(index.modules):
            facts = index.modules[relpath].get("trace") or {}
            if "table" in facts:
                table = {site: site for site in facts["table"]}
                anchor = (relpath, facts.get("doc_line", 1))
            for site, lineno in facts.get("fires", ()):
                fired.setdefault(site, (relpath, lineno))
        if table is None:
            return  # obs/tracing.py not in this scan (fixture runs)
        self._table, self._table_anchor = table, anchor
        self._fired = fired
        self.end_run(run)

    def end_run(self, run):
        if self._table is None:
            return  # obs/tracing.py not in this scan (fixture runs)
        anchor_path, anchor_line = self._table_anchor
        for site, (relpath, lineno) in sorted(self._fired.items()):
            if site not in self._table:
                run.report(
                    self,
                    relpath,
                    lineno,
                    "span {!r} is opened here but missing from the span-site "
                    "table in obs/tracing.py — add a ``{}``  row so operators "
                    "can find it in a merged timeline".format(site, site),
                )
        for site in sorted(set(self._table) - set(self._fired)):
            run.report(
                self,
                anchor_path,
                anchor_line,
                "span site {!r} is documented in the span-site table but "
                "never opened anywhere in the scanned code — stale row or "
                "missing span".format(site),
            )
