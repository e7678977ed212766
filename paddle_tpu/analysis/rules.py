"""graftlint rules GL001–GL011: framework-aware static checks.

Each rule encodes one invariant the runtime cannot cheaply enforce —
trace purity, host-sync hygiene, registry/doc consistency, lock
discipline, metric-name contract, span-name contract, lock-order
consistency, recompile hygiene, mutable-global capture, unguarded
shared state, guarded-by consistency — as a pure AST/text check. Rules
receive
the whole :class:`~paddle_tpu.analysis.core.Project` so cross-file rules
(GL003, GL005, GL006) see registrations and their catalogs together, and
the interprocedural rules (GL001/GL002/GL004 propagation, GL007, GL008,
and the GL010/GL011 lockset analysis in
:mod:`~paddle_tpu.analysis.locksets`) share one
:class:`~paddle_tpu.analysis.callgraph.CallGraph` per run via
``project.callgraph()``.

The rationale for each rule lives in docs/static_analysis.md; the short
form is on the rule class.
"""
from __future__ import annotations

import ast
import re

from .core import Finding, dotted_name


class Rule:
    id = "GL000"
    name = "base"
    rationale = ""

    def check(self, project):
        raise NotImplementedError

    def finding(self, srcfile, node, message, chain=()):
        return Finding(self.id, srcfile.relpath,
                       getattr(node, "lineno", 0),
                       getattr(node, "col_offset", 0),
                       message, scope=srcfile.scope_of(node), chain=chain)

    def strict_problems(self, project, findings=None):
        """Aggregator semantics (tools/run_static_checks.py): this one rule
        with NO baseline, inline suppressions honored. Pass ``findings`` to
        reuse an existing engine run."""
        from .core import partition, run

        if findings is None:
            findings = run(project, [self])
        else:
            findings = [f for f in findings if f.rule == self.id]
        new, _base, _supp = partition(project, findings, ())
        return [f"{f.path}:{f.line}: {f.message}" for f in new]


def _contains(node, pred):
    return any(pred(n) for n in ast.walk(node))


def _decorator_tag(dec):
    """'to_static' / 'defop' / 'jit' when the decorator compiles the body
    into a traced program, else None. Handles bare names, dotted paths,
    parameterized forms (@to_static(...)), and functools.partial(jax.jit)."""
    if isinstance(dec, ast.Call):
        fn = dotted_name(dec.func)
        if fn and fn.rsplit(".", 1)[-1] == "partial" and dec.args:
            return _decorator_tag(dec.args[0])
        dec = dec.func
    name = dotted_name(dec)
    if name is None:
        return None
    last = name.rsplit(".", 1)[-1]
    if last == "to_static" or last.endswith("defop"):
        return last if last == "to_static" else "defop"
    if name in ("jax.jit", "jit") or name.endswith(".jax.jit"):
        return "jit"
    return None


class TraceImpurity(Rule):
    """GL001: host-impure calls inside traced function bodies.

    A function compiled by ``to_static``/``defop``/``jax.jit`` runs its
    Python body ONCE, at trace time (jit/api.py:32 graph-break contract):
    ``time.time()``, ``datetime.now()``, ``np.random.*`` and file I/O
    evaluate to one concrete value that is then baked into the compiled
    program for every later call — a silent wrong-result bug, not a crash.
    Use ``monitor.now_ns`` outside the traced region for timing and the
    framework RNG (``paddle.seed`` / keyed ``jax.random``) for randomness.
    """

    id = "GL001"
    name = "trace-impurity"
    rationale = ("impure host calls in traced bodies run once and bake "
                 "their value into the compiled program")

    IMPURE_EXACT = {
        "time.time", "time.time_ns", "time.perf_counter",
        "time.perf_counter_ns", "time.monotonic", "time.monotonic_ns",
        "datetime.now", "datetime.utcnow", "datetime.datetime.now",
        "datetime.datetime.utcnow", "os.urandom", "uuid.uuid4",
        "open", "input",
    }
    IMPURE_PREFIX = ("np.random.", "numpy.random.", "random.")

    def _impure(self, call):
        name = dotted_name(call.func)
        if name is None:
            return None
        if name in self.IMPURE_EXACT:
            return name
        for p in self.IMPURE_PREFIX:
            if name.startswith(p):
                return name
        return None

    @staticmethod
    def _traced_functions(srcfile):
        """{FunctionDef: tag} for every function the file compiles into a
        traced program — decorator form (@to_static/@defop/@jax.jit) AND
        call form (``jax.jit(run, ...)`` / ``to_static(fn)``), which is
        how the serving engine builds its cached programs. Call-form
        targets resolve to the def with the same name in the same
        enclosing scope (two methods may each define a local ``run``).
        Memoized per file: three rules (GL001, GL002 interproc, GL008)
        share one computation."""
        memo = getattr(srcfile, "_traced_functions_memo", None)
        if memo is not None:
            return memo
        traced = {}
        defs = {}
        for n in srcfile.walk():
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault((n.name, srcfile.scope_of(n)), []).append(n)
                tags = [t for t in map(_decorator_tag, n.decorator_list)
                        if t]
                if tags:
                    traced.setdefault(n, tags[0])
        for call in srcfile.walk():
            if not isinstance(call, ast.Call) or not call.args:
                continue
            tag = _decorator_tag(call)
            arg = call.args[0]
            if tag and isinstance(arg, ast.Name):
                cands = defs.get((arg.id, srcfile.scope_of(call)), ())
                if len(cands) == 1:
                    traced.setdefault(cands[0], tag)
        srcfile._traced_functions_memo = traced
        return traced

    def check(self, project):
        out = []
        cg = project.callgraph()
        for f in project.files:
            if f.tree is None:
                continue
            for fn, tag in self._traced_functions(f).items():
                for call in ast.walk(fn):
                    if not isinstance(call, ast.Call):
                        continue
                    name = self._impure(call)
                    if name:
                        out.append(self.finding(
                            f, call,
                            f"trace-impure call {name}() inside "
                            f"@{tag} function '{fn.name}': evaluated "
                            "once at trace time and baked into the "
                            "compiled program"))
                        continue
                    # interprocedural: the impurity hides behind a helper
                    fi = cg.info_for_node(fn)
                    if fi is None:
                        continue
                    tgt = cg.resolve(f, fi.qualname, call)
                    if tgt is None or tgt == fi.key:
                        continue
                    entry = cg.callee_summary(tgt, "impure")
                    if entry is None:
                        continue
                    eff = entry[0]
                    via = " -> ".join(cg.chain_names(tgt, "impure"))
                    out.append(self.finding(
                        f, call,
                        f"call into trace-impure helper reaches "
                        f"{eff.detail} (via {via}) inside @{tag} "
                        f"function '{fn.name}': evaluated once at trace "
                        "time and baked into the compiled program",
                        chain=cg.chain(tgt, "impure")))
        return out


class HostSync(Rule):
    """GL002: device→host syncs in the dispatch/serving hot paths.

    ``.item()`` / ``.numpy()`` / ``float(jnp...)`` / ``np.asarray(jnp...)``
    each block until the device value materializes on host — one hidden
    round-trip per call, which serializes the async dispatch pipeline when
    it sits in an op wrapper or a decode loop. The documented exception is
    the API-normalization idiom guarded by ``isinstance(x, Tensor)`` /
    ``hasattr(x, "numpy")`` (Tensor-valued shape/axis arguments are a
    graph-break point by contract, jit/api.py:32).
    """

    id = "GL002"
    name = "host-sync-in-hot-path"
    rationale = ("each host read blocks the async device pipeline; hot "
                 "paths must batch or hoist them")

    SCOPES = ("paddle_tpu/ops/", "paddle_tpu/models/")
    CASTS = {"float", "int", "bool"}
    NP_COPIES = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
    # dtype/shape introspection runs on host metadata — no device value is
    # ever materialized, so casting these is not a sync
    METADATA = {"jnp.issubdtype", "jnp.promote_types", "jnp.result_type",
                "jnp.iinfo", "jnp.finfo", "jnp.dtype", "jnp.ndim",
                "jnp.shape"}
    METADATA_PREFIX = ("jax.tree_util.", "jax.errors.")

    @staticmethod
    def _is_guard_call(n):
        if not isinstance(n, ast.Call):
            return False
        fname = dotted_name(n.func)
        if fname == "isinstance" and len(n.args) == 2:
            return _contains(
                n.args[1],
                lambda m: (isinstance(m, ast.Name)
                           and m.id in ("Tensor", "ndarray"))
                or (isinstance(m, ast.Attribute)
                    and m.attr in ("Tensor", "ndarray")))
        if fname in ("hasattr", "getattr") and len(n.args) >= 2:
            arg = n.args[1]
            return (isinstance(arg, ast.Constant)
                    and arg.value in ("numpy", "value", "item"))
        return False

    @classmethod
    def _guard_polarity(cls, test):
        """True when the test asserts the guard (``isinstance(x, Tensor)``
        → the BODY branch is the guarded one), False when negated
        (``not isinstance(...)`` → the ORELSE branch is), None when the
        test is no guard at all."""
        for n in ast.walk(test):
            if cls._is_guard_call(n):
                negs = sum(1 for m in ast.walk(test)
                           if isinstance(m, ast.UnaryOp)
                           and isinstance(m.op, ast.Not)
                           and _contains(m.operand, cls._is_guard_call))
                return negs % 2 == 0
        return None

    def _guarded(self, srcfile, node):
        """True when `node` sits in the branch an isinstance/hasattr guard
        actually selects — a sync in the OTHER branch (the else of
        ``if isinstance(x, Tensor):``) is exactly the unguarded case."""
        child = node
        for anc in srcfile.ancestors(node):
            if isinstance(anc, (ast.If, ast.IfExp)):
                polarity = self._guard_polarity(anc.test)
                if polarity is not None:
                    branch = anc.body if polarity else anc.orelse
                    nodes = branch if isinstance(branch, list) else [branch]
                    if any(child is b for b in nodes):
                        return True
            child = anc
        return False

    @classmethod
    def _has_device_expr(cls, node):
        def pred(n):
            if isinstance(n, ast.Call):
                name = dotted_name(n.func)
                if name and (name.startswith("jnp.")
                             or name.startswith("jax.")) \
                        and name not in cls.METADATA \
                        and not name.startswith(cls.METADATA_PREFIX):
                    return True
            return False

        return _contains(node, pred)

    def check(self, project):
        out = []
        for f in project.files:
            if f.tree is None or not f.relpath.startswith(self.SCOPES):
                continue
            for call in f.walk():
                if not isinstance(call, ast.Call):
                    continue
                msg = self._classify(f, call)
                if msg:
                    out.append(self.finding(f, call, msg))
        out.extend(self._interprocedural(project))
        return out

    def _interprocedural(self, project):
        """Syncs hiding behind helper calls. Two propagation surfaces:

        1. a hot-path function (``SCOPES``) calling a helper OUTSIDE the
           hot-path scopes whose body (transitively) host-syncs — the sync
           site itself is not directly flagged, so the call site is;
        2. a traced (``to_static``/``defop``/``jit``) body calling a
           syncing helper anywhere — a host read under the trace is a
           concretization error at runtime; the lint catches it at review
           time.

        Suppressed or isinstance-guarded syncs never propagate (the
        callgraph drops them at effect collection)."""
        cg = project.callgraph()
        out = []
        seen = set()

        def emit(f, call, tgt, context):
            entry = cg.callee_summary(tgt, "hostsync")
            if entry is None:
                return
            key = (f.relpath, call.lineno, call.col_offset, tgt)
            if key in seen:
                return
            seen.add(key)
            eff = entry[0]
            via = " -> ".join(cg.chain_names(tgt, "hostsync"))
            out.append(self.finding(
                f, call,
                f"call into host-syncing helper reaches {eff.detail} "
                f"(via {via}) {context}",
                chain=cg.chain(tgt, "hostsync")))

        for fi in cg.functions.values():
            if not fi.path.startswith(self.SCOPES):
                continue
            f = fi.srcfile
            for (call, tgt, _disp) in fi.calls:
                if tgt is None or tgt == fi.key:
                    continue
                if cg.functions[tgt].path.startswith(self.SCOPES):
                    continue    # the sync site is directly flagged there
                if self._classify(f, call) or self._guarded(f, call):
                    continue
                emit(f, call, tgt,
                     "in a hot path; hoist the read out or keep the "
                     "reduction on device")

        from .callgraph import body_walk

        for f in project.files:
            if f.tree is None:
                continue
            for fn, tag in TraceImpurity._traced_functions(f).items():
                fi = cg.info_for_node(fn)
                if fi is None:
                    continue
                for call in body_walk(fn):
                    if not isinstance(call, ast.Call):
                        continue
                    tgt = cg.resolve(f, fi.qualname, call)
                    if tgt is None or tgt == fi.key:
                        continue
                    emit(f, call, tgt,
                         f"inside @{tag} function '{fn.name}': a host "
                         "read under the trace is a concretization "
                         "error; hoist it out of the compiled region")
        return out

    def _classify(self, srcfile, call):
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in ("item", "numpy"):
            # .numpy().item(): one sync, one finding (at the .numpy())
            recv = call.func.value
            if isinstance(recv, ast.Call) \
                    and isinstance(recv.func, ast.Attribute) \
                    and recv.func.attr == "numpy":
                return None
            if self._guarded(srcfile, call):
                return None
            return (f".{call.func.attr}() forces a device→host sync in a "
                    "hot path; hoist it out of the loop or guard it with "
                    "the isinstance(x, Tensor) normalization idiom")
        name = dotted_name(call.func)
        if name in self.CASTS and len(call.args) == 1 \
                and self._has_device_expr(call.args[0]) \
                and not self._guarded(srcfile, call):
            return (f"{name}(<device expr>) concretizes a jax value on "
                    "host (hidden sync); keep the reduction on device or "
                    "hoist the read out of the hot path")
        if name in self.NP_COPIES and call.args \
                and self._has_device_expr(call.args[0]) \
                and not self._guarded(srcfile, call):
            return (f"{name}(<device expr>) copies a device value to host "
                    "(hidden sync); compute it inside the compiled program "
                    "and transfer only the result")
        return None


class RegistryConsistency(Rule):
    """GL003: the defop registry, docs/ops.md, and AMP metadata agree.

    ``defop`` registrations ARE the op registry (ops/_apply.py:429);
    docs/ops.md is its generated, reviewed rendering. An op registered in
    source but absent from the doc (or carrying a different AMP category)
    means the doc — which the AMP auto-cast policy and reviewers read — is
    stale. Dynamic registrations (f-string names) make the reverse
    direction undecidable statically, so stale-row checks only run on
    trees with fully-literal registration.
    """

    id = "GL003"
    name = "registry-consistency"
    rationale = ("docs/ops.md and AMP categories must track the defop "
                 "registry or reviewers act on stale op metadata")

    AMP_CATEGORIES = {"white", "black", "fp32"}
    DOC = "docs/ops.md"
    _ROW = re.compile(r"^\|\s*`([^`]+)`\s*\|")
    _COUNT = re.compile(r"^(\d+) ops registered")

    @staticmethod
    def _reg_call(call):
        """(kind, name_node) for defop/register_op calls; plumbing
        (the generic call inside the defop/register_op definitions) is
        excluded by the caller via scope."""
        name = dotted_name(call.func)
        if name is None:
            return None
        last = name.rsplit(".", 1)[-1]
        if last.endswith("defop") or last == "register_op":
            return last
        return None

    def check(self, project):
        doc_text = project.read_optional(self.DOC)
        if doc_text is None:
            return []
        doc_rows, doc_count, count_line = self._parse_doc(doc_text)

        regs = []        # (srcfile, call, name, amp or None, amp_known)
        dynamic = []
        for f in project.files:
            if f.tree is None:
                continue
            for call in f.walk():
                if not isinstance(call, ast.Call) or not self._reg_call(call):
                    continue
                scope = f.scope_of(call)
                if scope.rsplit(".", 1)[-1] in ("defop", "register_op",
                                                "deco"):
                    continue  # the registry plumbing itself
                if not call.args or not isinstance(call.args[0], ast.Constant) \
                        or not isinstance(call.args[0].value, str):
                    dynamic.append((f, call))
                    continue
                amp, amp_known = None, True
                for kw in call.keywords:
                    if kw.arg == "amp_category":
                        if isinstance(kw.value, ast.Constant):
                            amp = kw.value.value
                        else:
                            amp_known = False
                regs.append((f, call, call.args[0].value, amp, amp_known))

        out = []
        seen = {}
        for f, call, name, amp, amp_known in regs:
            if name in seen:
                out.append(self.finding(
                    f, call,
                    f"op '{name}' registered twice (also at "
                    f"{seen[name]}); the registry is a name-keyed "
                    "contract, the second registration silently wins"))
            else:
                seen[name] = f"{f.relpath}:{call.lineno}"
            if amp is not None and amp not in self.AMP_CATEGORIES:
                out.append(self.finding(
                    f, call,
                    f"op '{name}' has unknown amp_category {amp!r} "
                    f"(expected one of {sorted(self.AMP_CATEGORIES)})"))
            if name not in doc_rows:
                out.append(self.finding(
                    f, call,
                    f"op '{name}' registered here but has no row in "
                    f"{self.DOC} — regenerate it with "
                    "`python -m paddle_tpu.ops.optable`"))
            elif amp_known and (amp or "-") != doc_rows[name][1]:
                out.append(self.finding(
                    f, call,
                    f"op '{name}' amp_category={(amp or '-')!r} here but "
                    f"{self.DOC} says {doc_rows[name][1]!r} — stale doc, "
                    "regenerate it"))
        if not dynamic:
            for name, (line, _amp) in sorted(doc_rows.items()):
                if name not in seen:
                    out.append(Finding(
                        self.id, self.DOC, line, 0,
                        f"doc row for op '{name}' has no registration in "
                        "the source tree — stale doc, regenerate it"))
        if doc_count is not None and doc_count != len(doc_rows):
            out.append(Finding(
                self.id, self.DOC, count_line, 0,
                f"doc header claims {doc_count} ops but the table has "
                f"{len(doc_rows)} rows — regenerate it"))
        return out

    def _parse_doc(self, text):
        rows, count, count_line = {}, None, 0
        for i, line in enumerate(text.splitlines(), 1):
            m = self._ROW.match(line)
            if m and m.group(1) != "op":
                cols = [c.strip() for c in line.strip().strip("|").split("|")]
                amp = cols[-1] if len(cols) >= 4 else "-"
                rows[m.group(1)] = (i, amp)
                continue
            m = self._COUNT.match(line)
            if m:
                count, count_line = int(m.group(1)), i
        return rows, count, count_line


class LockDiscipline(Rule):
    """GL004: no device dispatch or blocking wait inside a lock body.

    ``with self._lock:`` bodies must be short, host-only critical
    sections: a ``jax.*``/``jnp.*`` call under the lock can block on
    device execution (or worse, re-enter instrumented dispatch that takes
    the same lock), and ``time.sleep``/``.join()``/``.wait()`` turn the
    metric registry or serving engine into a convoy. Move device work and
    waits outside, keep only the state mutation inside.
    """

    id = "GL004"
    name = "lock-discipline"
    rationale = ("device dispatch or blocking waits under a lock convoy "
                 "every other thread touching that lock")

    BLOCKING_ATTRS = {"join", "wait", "acquire", "result"}
    BLOCKING_EXACT = {"time.sleep"}

    @staticmethod
    def _lock_ctx(item):
        name = dotted_name(item.context_expr)
        return name is not None and name.rsplit(".", 1)[-1].lower().endswith(
            "lock")

    def check(self, project):
        out = []
        for f in project.files:
            if f.tree is None:
                continue
            for w in f.walk():
                if not isinstance(w, ast.With) \
                        or not any(self._lock_ctx(i) for i in w.items):
                    continue
                lock = next(dotted_name(i.context_expr) for i in w.items
                            if self._lock_ctx(i))
                for call in ast.walk(w):
                    msg = self._classify(call, lock)
                    if msg:
                        out.append(self.finding(f, call, msg))
        # interprocedural: a helper called under the lock blocks/dispatches
        cg = project.callgraph()
        for fi in cg.functions.values():
            f = fi.srcfile
            for (lockkey, _w, _inner, calls) in fi.lock_regions:
                lock = lockkey.split(":", 1)[-1]
                for (call, tgt, disp) in calls:
                    if tgt == fi.key:
                        continue
                    if self._classify(call, lock):
                        continue    # directly flagged above
                    entry = cg.callee_summary(tgt, "blocking")
                    if entry is None:
                        continue
                    eff = entry[0]
                    via = " -> ".join(cg.chain_names(tgt, "blocking"))
                    out.append(self.finding(
                        f, call,
                        f"call into blocking helper reaches {eff.detail} "
                        f"(via {via}) inside `with {lock}:` — every other "
                        "thread touching the lock convoys behind it; move "
                        "the call outside the critical section",
                        chain=cg.chain(tgt, "blocking")))
        return out

    @classmethod
    def _blocking_attr_call(cls, call):
        """True for ``.join()``/``.wait()``/``.acquire()``/``.result()``
        shapes that actually block: zero args or a single numeric timeout.
        ``os.path.join(a, b)`` / ``sep.join(parts)`` take value arguments
        and are pure — the arity is the distinguisher."""
        if not isinstance(call.func, ast.Attribute) \
                or call.func.attr not in cls.BLOCKING_ATTRS \
                or isinstance(call.func.value, ast.Constant):
            return False
        if call.keywords:
            return True         # .wait(timeout=...) etc.
        if len(call.args) == 0:
            return True
        if len(call.args) == 1:  # numeric literal = a timeout, not a value
            a = call.args[0]
            return isinstance(a, ast.Constant) \
                and isinstance(a.value, (int, float))
        return False

    def _classify(self, call, lock):
        if not isinstance(call, ast.Call):
            return None
        name = dotted_name(call.func)
        if name and (name.startswith("jax.") or name.startswith("jnp.")):
            return (f"device dispatch {name}() inside `with {lock}:` can "
                    "block on the device (or re-enter instrumented "
                    "dispatch) while every other thread waits on the lock")
        if name in self.BLOCKING_EXACT:
            return (f"{name}() sleeps while holding `{lock}` — every "
                    "other thread touching the lock convoys behind it")
        if self._blocking_attr_call(call):
            return (f".{call.func.attr}() blocks while holding `{lock}`; "
                    "wait outside the critical section")
        return None


class MetricNameContract(Rule):
    """GL005: the telemetry metric-name contract (absorbs
    tools/check_metric_names.py, whose CLI stays as a thin shim).

    Every ``paddle_tpu_*`` metric registered anywhere in the tree must be
    declared in ``paddle_tpu/monitor/catalog.py`` and follow the
    ``paddle_tpu_<subsystem>_<name>`` convention (counters end ``_total``)
    — dashboards and artifact validators key on these exact strings, so an
    undeclared or misnamed metric is a contract break, not a style issue.
    """

    id = "GL005"
    name = "metric-name-contract"
    rationale = ("metric names are a dashboard-facing contract; "
                 "undeclared or misnamed series break consumers silently")

    CATALOG = "paddle_tpu/monitor/catalog.py"
    REG_FUNCS = {"counter", "gauge", "histogram"}
    KINDS = ("counter", "gauge", "histogram")

    @staticmethod
    def load_catalog(path):
        """Execute the (dependency-free by design) catalog module by file
        path — shared with the tools/check_metric_names.py shim."""
        import importlib.util

        spec = importlib.util.spec_from_file_location("_graftlint_catalog",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def strict_problems(self, project, findings=None):
        """The PR 1 check_metric_names semantics, in one place for both
        the shim CLI and the run_static_checks aggregator: no baseline,
        inline suppressions honored, and a MISSING catalog is a failure
        (the rule itself skips quietly on catalog-less fixture trees).
        Pass ``findings`` to reuse an existing engine run."""
        from .core import partition, run

        if project.read_optional(self.CATALOG) is None:
            return [f"{self.CATALOG}: catalog not found under "
                    f"{project.root} — the metric-name contract cannot "
                    "be checked"]
        if findings is None:
            findings = run(project, [self])
        else:
            findings = [f for f in findings if f.rule == self.id]
        new, _base, _supp = partition(project, findings, ())
        return [f"{f.path}:{f.line}: {f.message}" for f in new]

    def check(self, project):
        if project.read_optional(self.CATALOG) is None:
            return []
        import os

        cat = self.load_catalog(os.path.join(project.root, self.CATALOG))
        name_re = re.compile(cat.NAME_PATTERN)
        out = []
        catfile = next((f for f in project.files
                        if f.relpath == self.CATALOG), None)

        def cat_line(name):
            if catfile is None:
                return 0
            for i, line in enumerate(catfile.lines, 1):
                if f'"{name}"' in line:
                    return i
            return 0

        for name, (kind, _labels, help_text) in sorted(cat.METRICS.items()):
            loc = cat_line(name)
            if not name_re.match(name):
                out.append(Finding(
                    self.id, self.CATALOG, loc, 0,
                    f"catalog name {name} does not match paddle_tpu_"
                    f"<{'|'.join(cat.SUBSYSTEMS)}>_<name>"))
            if kind == "counter" and not name.endswith("_total"):
                out.append(Finding(
                    self.id, self.CATALOG, loc, 0,
                    f"catalog counter {name} must end in _total"))
            if kind not in self.KINDS:
                out.append(Finding(
                    self.id, self.CATALOG, loc, 0,
                    f"catalog name {name} has unknown type {kind!r}"))
            if not help_text:
                out.append(Finding(
                    self.id, self.CATALOG, loc, 0,
                    f"catalog name {name} has no help text"))

        declared = set(cat.METRICS)
        for f in project.files:
            if f.tree is None:
                continue
            for call in f.walk():
                if not isinstance(call, ast.Call) or not call.args:
                    continue
                fname = dotted_name(call.func)
                if fname is None \
                        or fname.rsplit(".", 1)[-1] not in self.REG_FUNCS:
                    continue
                arg = call.args[0]
                if not (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)
                        and arg.value.startswith("paddle_tpu_")):
                    continue
                name = arg.value
                if name not in declared:
                    out.append(self.finding(
                        f, call,
                        f"metric {name} registered but not declared in "
                        f"{self.CATALOG}"))
                elif not name_re.match(name):
                    out.append(self.finding(
                        f, call,
                        f"metric {name} violates the naming convention "
                        f"{cat.NAME_PATTERN}"))
        return out


class SpanNameContract(Rule):
    """GL006: the trace span-name contract (the GL005 of the span layer).

    Every span the framework emits (``monitor/trace.py``) must be declared
    in ``paddle_tpu/monitor/catalog.py`` ``SPANS`` and follow the
    ``<subsystem>.<name>`` convention — trace viewers, flight-recorder
    consumers and the hang-dump workflow key on the exact strings, so an
    undeclared or misnamed span is a contract break, not a style issue.
    """

    id = "GL006"
    name = "span-name-contract"
    rationale = ("span names are a trace-viewer/hang-dump contract; "
                 "undeclared or misnamed spans break consumers silently")

    CATALOG = "paddle_tpu/monitor/catalog.py"
    # functions whose first string-literal argument is a span name
    # (phase / _next_phase / then: the trace.phase() helper and the
    # serving engine's hand-over between a step's phases)
    EMIT_FUNCS = {"span", "start_span", "record_span", "phase",
                  "_next_phase", "then"}

    load_catalog = staticmethod(MetricNameContract.load_catalog)

    def strict_problems(self, project, findings=None):
        """Aggregator semantics (tools/run_static_checks.py): no baseline,
        inline suppressions honored, and a catalog without a SPANS table is
        a failure (the rule itself skips quietly on span-less fixture
        trees). Pass ``findings`` to reuse an existing engine run."""
        from .core import partition, run

        if project.read_optional(self.CATALOG) is None:
            return [f"{self.CATALOG}: catalog not found under "
                    f"{project.root} — the span-name contract cannot "
                    "be checked"]
        import os

        cat = self.load_catalog(os.path.join(project.root, self.CATALOG))
        if getattr(cat, "SPANS", None) is None:
            return [f"{self.CATALOG}: no SPANS table — the span-name "
                    "contract cannot be checked"]
        if findings is None:
            findings = run(project, [self])
        else:
            findings = [f for f in findings if f.rule == self.id]
        new, _base, _supp = partition(project, findings, ())
        return [f"{f.path}:{f.line}: {f.message}" for f in new]

    def check(self, project):
        if project.read_optional(self.CATALOG) is None:
            return []
        import os

        cat = self.load_catalog(os.path.join(project.root, self.CATALOG))
        spans = getattr(cat, "SPANS", None)
        if spans is None:
            return []   # metric-only fixture catalog: nothing to enforce
        subsystems = tuple(getattr(cat, "SPAN_SUBSYSTEMS", ()))
        name_re = re.compile(getattr(
            cat, "SPAN_PATTERN",
            r"^(" + "|".join(subsystems) + r")(\.[a-z][a-z0-9_]*)+$"))
        out = []
        catfile = next((f for f in project.files
                        if f.relpath == self.CATALOG), None)

        def cat_line(name):
            if catfile is None:
                return 0
            for i, line in enumerate(catfile.lines, 1):
                if f'"{name}"' in line:
                    return i
            return 0

        for name, help_text in sorted(spans.items()):
            loc = cat_line(name)
            if not name_re.match(name):
                out.append(Finding(
                    self.id, self.CATALOG, loc, 0,
                    f"catalog span {name} does not match "
                    f"<{'|'.join(subsystems)}>.<name>"))
            if not help_text:
                out.append(Finding(
                    self.id, self.CATALOG, loc, 0,
                    f"catalog span {name} has no help text"))

        declared = set(spans)
        for f in project.files:
            if f.tree is None:
                continue
            for call in f.walk():
                if not isinstance(call, ast.Call) or not call.args:
                    continue
                fname = dotted_name(call.func)
                if fname is not None:
                    last = fname.rsplit(".", 1)[-1]
                elif isinstance(call.func, ast.Attribute):
                    # non-dotted receivers too (mon[5].record_span(...) —
                    # the lazily-bound handle tuples of the instrument
                    # sites): the method name alone identifies an emitter
                    last = call.func.attr
                else:
                    continue
                if last not in self.EMIT_FUNCS:
                    continue
                arg = call.args[0]
                if not (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)
                        and "." in arg.value
                        and arg.value.split(".", 1)[0] in subsystems):
                    continue    # dynamic names / foreign span() calls
                name = arg.value
                if name not in declared:
                    out.append(self.finding(
                        f, call,
                        f"span {name} emitted but not declared in "
                        f"{self.CATALOG} SPANS"))
                elif not name_re.match(name):
                    out.append(self.finding(
                        f, call,
                        f"span {name} violates the naming convention "
                        f"{name_re.pattern}"))
        return out


class LockOrder(Rule):
    """GL007: lock-order inversion across the runtime stack.

    The serving engine, watchdog scanner, dataloader producer and monitor
    exporters run as concurrent threads sharing a handful of locks. A
    deadlock needs no bug in any single function — only two call paths
    acquiring the same two locks in opposite orders. The call graph makes
    the acquisition order static: ``with lockA:`` whose body (transitively,
    through helpers) acquires ``lockB`` is an A→B edge; any cycle in that
    graph is a potential deadlock and every participating order must be
    made consistent. The runtime twin is graftsan's lock-order witness
    (``analysis/sanitizers.py``), which checks the ACTUAL acquisition
    orders the process performs.
    """

    id = "GL007"
    name = "lock-order-inversion"
    rationale = ("two paths acquiring the same locks in opposite orders "
                 "deadlock under the right interleaving; the acquisition "
                 "graph must stay acyclic")

    def check(self, project):
        cg = project.callgraph()
        edges = {}   # (a, b) -> (srcfile, node, via text, chain)
        for fi in cg.functions.values():
            for (lockkey, w, inner, calls) in fi.lock_regions:
                if fi.srcfile.suppressed(self.id, w.lineno):
                    continue
                for (k, line) in inner:
                    if k != lockkey:
                        edges.setdefault((lockkey, k), (
                            fi.srcfile, w,
                            f"{fi.qualname} nests the acquisitions",
                            (f"{fi.qualname} acquires "
                             f"{_lk(lockkey)} at {fi.path}:{w.lineno} then "
                             f"{_lk(k)} at {fi.path}:{line}",)))
                for (call, tgt, disp) in calls:
                    if tgt == fi.key:
                        continue
                    for k in cg.transitive_acquires(tgt):
                        if k == lockkey:
                            continue
                        hops = cg.chain(tgt, "acquire:" + k)
                        edges.setdefault((lockkey, k), (
                            fi.srcfile, call,
                            f"{fi.qualname} calls {disp}",
                            (f"{fi.qualname} holds {_lk(lockkey)} and "
                             f"calls {disp} at {fi.path}:{call.lineno}",)
                            + tuple(hops)))
        return self._cycle_findings(edges)

    def _cycle_findings(self, edges):
        adj = {}
        for (a, b) in edges:
            adj.setdefault(a, set()).add(b)
        out = []
        reported = set()
        for (a, b) in sorted(edges):
            if (b, a) not in edges:
                continue
            pair = tuple(sorted((a, b)))
            if pair in reported:
                continue
            reported.add(pair)
            f1, n1, via1, chain1 = edges[(pair[0], pair[1])]
            f2, n2, via2, chain2 = edges[(pair[1], pair[0])]
            out.append(Finding(
                self.id, f1.relpath, getattr(n1, "lineno", 0),
                getattr(n1, "col_offset", 0),
                f"lock-order inversion: {_lk(pair[0])} -> {_lk(pair[1])} "
                f"({via1}) but {_lk(pair[1])} -> {_lk(pair[0])} ({via2}) — "
                "a deadlock under the right interleaving; pick one order "
                "and make every path follow it",
                scope=f1.scope_of(n1),
                chain=tuple(chain1) + ("-- versus --",) + tuple(chain2)))
        # longer cycles: walk each simple cycle not already covered by a
        # pairwise inversion (rotation-canonical so each reports once)
        for cyc in self._simple_cycles(adj):
            if len(cyc) == 2:
                continue
            canon = tuple(sorted(cyc))
            if canon in reported:
                continue
            reported.add(canon)
            first = min(cyc)
            i = cyc.index(first)
            order = cyc[i:] + cyc[:i]
            f1, n1, _via, _chain = edges[(order[0], order[1])]
            chain = []
            for x, y in zip(order, order[1:] + order[:1]):
                chain.extend(edges[(x, y)][3])
            out.append(Finding(
                self.id, f1.relpath, getattr(n1, "lineno", 0),
                getattr(n1, "col_offset", 0),
                "lock-order cycle: "
                + " -> ".join(_lk(k) for k in order + (order[0],))
                + " — a deadlock under the right interleaving; break the "
                "cycle by fixing one global acquisition order",
                scope=f1.scope_of(n1), chain=tuple(chain)))
        out.sort(key=lambda x: (x.path, x.line))
        return out

    @staticmethod
    def _simple_cycles(adj):
        """Bounded DFS enumeration of simple cycles (the lock graph is tiny
        — a handful of nodes — so exhaustive search is fine)."""
        cycles = []
        seen = set()
        nodes = sorted(adj)

        def dfs(start, cur, path):
            for nxt in sorted(adj.get(cur, ())):
                if nxt == start and len(path) > 1:
                    canon = tuple(sorted(path))
                    if canon not in seen:
                        seen.add(canon)
                        cycles.append(tuple(path))
                elif nxt not in path and nxt > start and len(path) < 8:
                    dfs(start, nxt, path + [nxt])

        for n in nodes:
            dfs(n, n, [n])
        return cycles


def _lk(lockkey):
    """Human form of a lock key (drop the file prefix when unambiguous)."""
    return lockkey.split(":", 1)[-1]


class RecompileHazard(Rule):
    """GL008: recompile storms visible from the source.

    Whole-program compilation makes compile count the hidden cost center
    (arxiv 2301.13062): each new signature pays a trace + XLA compile that
    dwarfs the step it serves. Three statically-visible hazard shapes, each
    a bug class this tree has actually shipped (PR 2 found the first by
    hand):

    1. **per-call registration** — a ``@defop`` inside a function body
       whose wrapper is called in that same body re-registers the op per
       call: a fresh OpDef identity per call defeats the per-signature vjp
       cache (every backward retraces) and churns the registry. Factories
       that REGISTER inside a helper but return the wrapper uncalled are
       fine (registration runs once at import).
    2. **shape/dtype branching in a jitted body** — ``if x.shape[0] > n:``
       inside a ``to_static``/``jax.jit`` body compiles one program per
       outcome; with unbucketed shapes that is one compile per distinct
       shape (the recompile storm the serving engine's prefill buckets
       exist to prevent). ``defop`` bodies are exempt: eager ops are
       per-signature cached by design and shape normalization there is the
       norm.
    3. **per-call-constructed static args** — passing a ``lambda`` (or a
       function defined in the calling function's body) to a compiled
       callable keys the program cache on the object's ``repr`` — a fresh
       address every call, so every call is a cache miss that compiles.

    The runtime twin is graftsan's recompile sentinel
    (``analysis/sanitizers.py``), which counts actual cache misses and
    trips past a threshold.
    """

    id = "GL008"
    name = "recompile-hazard"
    rationale = ("every avoidable signature is a trace+compile that dwarfs "
                 "the step it serves; registration, branching and cache "
                 "keys must be compile-stable")

    SHAPE_ATTRS = {"shape", "ndim", "dtype"}

    def check(self, project):
        out = []
        for f in project.files:
            if f.tree is None:
                continue
            out.extend(self._per_call_registration(f))
            out.extend(self._shape_branching(f))
            out.extend(self._weak_static_args(f))
        return out

    # -- pattern 1: per-call registration ------------------------------------
    def _per_call_registration(self, f):
        out = []
        for node in f.walk():
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(self._is_reg_decorator(d) for d in node.decorator_list):
                continue
            owner = self._enclosing_function(f, node)
            if owner is None:
                continue    # module/class level: registered once at import
            from .callgraph import body_walk

            called = any(
                isinstance(c, ast.Call) and isinstance(c.func, ast.Name)
                and c.func.id == node.name
                for c in body_walk(owner))
            if called:
                out.append(self.finding(
                    f, node,
                    f"op '{node.name}' is @defop-registered inside "
                    f"'{owner.name}' and called there: re-registered on "
                    "EVERY call — a fresh OpDef identity defeats the "
                    "per-signature vjp cache (each backward retraces) and "
                    "churns the registry; hoist the registration to module "
                    "level"))
        return out

    @staticmethod
    def _is_reg_decorator(dec):
        if isinstance(dec, ast.Call):
            dec = dec.func
        name = dotted_name(dec)
        return name is not None and (
            name.rsplit(".", 1)[-1].endswith("defop")
            or name.rsplit(".", 1)[-1] == "register_op")

    @staticmethod
    def _enclosing_function(f, node):
        for anc in f.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc
        return None

    # -- pattern 2: shape/dtype branching in jitted bodies -------------------
    def _shape_branching(self, f):
        from .callgraph import body_walk

        out = []
        for fn, tag in TraceImpurity._traced_functions(f).items():
            if tag == "defop":
                continue    # eager ops are per-signature cached by design
            params = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
            params.discard("self")
            for node in body_walk(fn):
                if not isinstance(node, (ast.If, ast.While, ast.IfExp)):
                    continue
                hit = self._shape_test(node.test, params)
                if hit:
                    out.append(self.finding(
                        f, node,
                        f"branch on {hit} inside @{tag} function "
                        f"'{fn.name}': one compiled program per outcome — "
                        "with unbucketed inputs, one compile per distinct "
                        "shape (recompile storm); pad/bucket the input or "
                        "use a device-side select"))
        return out

    def _shape_test(self, test, params):
        for n in ast.walk(test):
            if isinstance(n, ast.Attribute) and n.attr in self.SHAPE_ATTRS \
                    and isinstance(n.value, ast.Name) \
                    and n.value.id in params:
                return f"{n.value.id}.{n.attr}"
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                    and n.func.id == "len" and len(n.args) == 1 \
                    and isinstance(n.args[0], ast.Name) \
                    and n.args[0].id in params:
                return f"len({n.args[0].id})"
        return None

    # -- pattern 3: per-call-constructed static args -------------------------
    def _weak_static_args(self, f):
        out = []
        compiled = self._compiled_names(f)
        if not compiled:
            return out
        local_defs = {}
        for n in f.walk():
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local_defs.setdefault(n.name, []).append(f.scope_of(n))
        for call in f.walk():
            if not isinstance(call, ast.Call) \
                    or not isinstance(call.func, ast.Name) \
                    or call.func.id not in compiled:
                continue
            scope = f.scope_of(call)
            for arg in list(call.args) + [k.value for k in call.keywords]:
                if isinstance(arg, ast.Lambda):
                    out.append(self.finding(
                        f, call,
                        f"lambda argument to compiled callable "
                        f"'{call.func.id}': the program cache keys "
                        "non-hashable constants by repr — a fresh object "
                        "address every call, so EVERY call is a compile; "
                        "hoist the function to module level"))
                elif isinstance(arg, ast.Name) and scope:
                    # a def in the calling function (or an enclosing one):
                    # fresh function object per outer call
                    nested = [s for s in local_defs.get(arg.id, ())
                              if s and (scope == s
                                        or scope.startswith(s + "."))]
                    if nested:
                        out.append(self.finding(
                            f, call,
                            f"locally-defined function '{arg.id}' passed "
                            f"to compiled callable '{call.func.id}': a "
                            "fresh function object per enclosing call "
                            "keys a new signature each time (recompile "
                            "storm); hoist it to module level"))
        return out

    @staticmethod
    def _compiled_names(f):
        """Local names statically known to be compiled callables: defs
        decorated @to_static/@jax.jit (not @defop), and assignment targets
        of ``to_static(...)`` / ``jax.jit(...)`` results."""
        names = set()
        for n in f.walk():
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                tags = [t for t in map(_decorator_tag, n.decorator_list) if t]
                if tags and tags[0] in ("to_static", "jit"):
                    names.add(n.name)
            elif isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
                tag = _decorator_tag(n.value)
                if tag in ("to_static", "jit"):
                    for tgt in n.targets:
                        if isinstance(tgt, ast.Name):
                            names.add(tgt.id)
        return names


class MutableGlobalCapture(Rule):
    """GL009: jitted/to_static bodies that close over a MUTABLE module
    global.

    A traced body runs its Python ONCE: reading a module-level list/
    dict/set bakes the values seen at trace time into the compiled
    program. Later mutations of the global are silently ignored — until
    an unrelated recompile (new shape, evicted cache) re-traces and
    picks them up, so behavior CHANGES at a point no code changed. That
    staleness-then-divergence is nastier than a plain wrong constant
    (GL001's territory) because it is green in every test that traces
    exactly once. Pass the value as an argument (retrace on change) or
    bind it to an immutable module constant.
    """

    id = "GL009"
    name = "mutable-global-capture"
    rationale = ("a traced body reading a mutable module global bakes "
                 "trace-time contents in; later mutations apply only "
                 "after an unrelated recompile")

    MUTABLE_CALLS = {"list", "dict", "set", "defaultdict", "OrderedDict",
                     "Counter", "deque", "bytearray"}

    def _mutable_globals(self, srcfile):
        """{name: kind} for module-level bindings whose value is a
        mutable container (display literal, comprehension, or a bare
        constructor call)."""
        out = {}
        for node in srcfile.tree.body:
            if isinstance(node, ast.Assign):
                targets = [t for t in node.targets
                           if isinstance(t, ast.Name)]
                value = node.value
            elif isinstance(node, ast.AnnAssign) \
                    and isinstance(node.target, ast.Name) \
                    and node.value is not None:
                targets = [node.target]
                value = node.value
            else:
                continue
            kind = None
            if isinstance(value, (ast.List, ast.ListComp)):
                kind = "list"
            elif isinstance(value, (ast.Dict, ast.DictComp)):
                kind = "dict"
            elif isinstance(value, (ast.Set, ast.SetComp)):
                kind = "set"
            elif isinstance(value, ast.Call):
                name = dotted_name(value.func)
                if name and name.rsplit(".", 1)[-1] in self.MUTABLE_CALLS:
                    kind = name.rsplit(".", 1)[-1]
            if kind:
                for t in targets:
                    out[t.id] = kind
        return out

    def check(self, project):
        out = []
        for f in project.files:
            if f.tree is None:
                continue
            mutables = self._mutable_globals(f)
            if not mutables:
                continue
            for fn, tag in TraceImpurity._traced_functions(f).items():
                # any name bound inside the function (params of every
                # kind, stores, comprehension targets, nested defs)
                # shadows the global
                bound = set()
                for n in ast.walk(fn):
                    a = getattr(n, "args", None)
                    if isinstance(a, ast.arguments):
                        for arg in (list(a.posonlyargs) + list(a.args)
                                    + list(a.kwonlyargs)
                                    + [x for x in (a.vararg, a.kwarg)
                                       if x is not None]):
                            bound.add(arg.arg)
                for n in ast.walk(fn):
                    if isinstance(n, ast.Name) \
                            and isinstance(n.ctx, (ast.Store, ast.Del)):
                        bound.add(n.id)
                    elif isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef,
                                        ast.ClassDef)) and n is not fn:
                        bound.add(n.name)
                seen = set()
                for n in ast.walk(fn):
                    if not (isinstance(n, ast.Name)
                            and isinstance(n.ctx, ast.Load)):
                        continue
                    kind = mutables.get(n.id)
                    if kind is None or n.id in bound or n.id in seen:
                        continue
                    seen.add(n.id)
                    out.append(self.finding(
                        f, n,
                        f"@{tag} function '{fn.name}' closes over "
                        f"mutable module-global '{n.id}' ({kind}): the "
                        "traced program bakes in the contents seen at "
                        "trace time, and later mutations apply only "
                        "after an unrelated recompile — pass it as an "
                        "argument or make it an immutable constant"))
        return out


class UnguardedSharedState(Rule):
    """GL010: a ``self.<attr>`` written under a lock anywhere in its
    class but accessed lock-free from a thread-reachable method.

    The write-under-lock is the author's own declaration that the field
    is shared mutable state; the lock-free access from a method another
    thread can reach is then a data race by the author's own contract.
    The static lockset at an access is the union of the enclosing
    ``with <lock>:`` regions, the locks provably held at every call site
    on the thread path (the ``*_locked`` helper convention), and any
    ``# guarded_by: <lock>`` annotation on the line. ``Finding.chain``
    carries the thread-entry chain — the ``Thread(target=...)`` spawn
    site and the call hops from it to the unguarded access — rendered by
    ``--explain`` exactly like the GL001/GL002 propagation chains.
    Deliberately lock-free fields (GIL-atomic monotonic counters,
    append-only telemetry) take ``# graftlint: disable=GL010`` with a
    rationale; externally synchronized ones take ``# guarded_by:``.
    """

    id = "GL010"
    name = "unguarded-shared-state"
    rationale = ("a field written under a lock is shared state by the "
                 "author's own declaration; touching it lock-free from "
                 "a thread-reachable method is a data race")

    def check(self, project):
        from .locksets import analysis_for

        out = []
        la = analysis_for(project)
        for (srcfile, access, cls, guard, root) in \
                la.unguarded_shared_state():
            method = la.cg.functions[access.method_key].qualname
            kind = "written" if access.write else "read"
            out.append(self.finding(
                srcfile, access.node,
                f"'self.{access.attr}' of class '{cls}' is written "
                f"under lock '{_lk(guard)}' elsewhere but {kind} "
                f"lock-free in '{method}', which runs on a thread "
                f"spawned via '{root}' — hold the lock here, or mark "
                "the line '# guarded_by: <lock>' if it is synchronized "
                "externally",
                chain=la.thread_chain(access.method_key)))
        return out


class GuardedByInconsistency(Rule):
    """GL011: lock/field associations that are internally contradictory.

    (a) the guarded writes of one attribute hold locksets with an empty
    common intersection — two sites each "hold a lock", but not the
    *same* lock, so neither excludes the other (this also catches a
    ``# guarded_by:`` annotation naming a lock the real writes never
    hold); (b) a mutable container built in ``__init__`` and mutated
    under a lock escapes that lock's region via a bare
    ``return self.<attr>`` / ``yield self.<attr>`` — the caller iterates
    or mutates the live object after the lock is released. Return a
    snapshot (``list(...)``, ``dict(...)``) instead.
    """

    id = "GL011"
    name = "guarded-by-inconsistency"
    rationale = ("a field guarded by different locks at different sites "
                 "is guarded by none; a mutable structure returned from "
                 "inside its lock region escapes the lock")

    def check(self, project):
        from .locksets import analysis_for

        out = []
        la = analysis_for(project)
        for (access, cls, menu, sites) in la.inconsistent_guards():
            fi = la.cg.functions[access.method_key]
            chain = tuple(
                f"write under {{{', '.join(_lk(l) for l in locks)}}} "
                f"at {fi.path}:{line}"
                for (line, locks) in sites)
            out.append(self.finding(
                fi.srcfile, access.node,
                f"'self.{access.attr}' of class '{cls}' is guarded by "
                f"different locks at different write sites "
                f"({', '.join(_lk(l) for l in menu)} — no common "
                "lock): every writer must hold the same lock for "
                "mutual exclusion to mean anything",
                chain=chain))
        for (srcfile, node, cls, attr, kind, lockkey) in \
                la.lock_region_escapes():
            out.append(self.finding(
                srcfile, node,
                f"mutable {kind} 'self.{attr}' of class '{cls}' "
                f"escapes the '{_lk(lockkey)}' region via a bare "
                "return/yield while being mutated under that lock "
                "elsewhere — the caller sees live unlocked state; "
                "return a copy instead"))
        return out


ALL_RULES = (TraceImpurity(), HostSync(), RegistryConsistency(),
             LockDiscipline(), MetricNameContract(), SpanNameContract(),
             LockOrder(), RecompileHazard(), MutableGlobalCapture(),
             UnguardedSharedState(), GuardedByInconsistency())

RULES_BY_ID = {r.id: r for r in ALL_RULES}
