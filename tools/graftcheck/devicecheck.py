"""Device hygiene analysis: jit-cache discipline, transfer hygiene,
donation audit (ISSUE 19).

The two failure modes that dominate TPU serving stacks are silent
recompilation (a corpus-dependent Python value leaking into a traced
shape, a static arg, or a jit-cache key turns the steady-state hot path
into a compile storm) and implicit host<->device synchronization
(``float()`` / ``.item()`` / ``np.asarray`` / truthiness on a device
array mid-dispatch stalls the pipeline the executor exists to overlap).
Three static passes guard them:

(a) **jit-cache discipline** — every ``jax.jit`` / ``partial(jit)`` /
    ``shard_map`` creation found anywhere in the package (the jitpurity
    root finder, extended with creation scope + call kwargs) must be
    reached through one of the accepted seams:

    * created at module import time (compiled-once by construction);
    * memo-stored into a subscripted cache (``self._fns[cap] = jit(…)``,
      the established "jit-cached per (capacity, k, chunk)" pattern) —
      and then the cache KEY must be capacity-class: corpus-dependent
      values (``.shape``, ``len()``, ``n_docs`` / ``nnz`` / … attrs)
      must pass through ``next_capacity`` (or be bounded by ``min``/
      ``max`` against a clean value) before keying the cache;
    * an ``lru_cache``-decorated factory;
    * created inside a function that is itself a jit root (trace-time
      creation — re-created only when the OUTER entry retraces);
    * a factory that returns the jit (or a nested jitted def) to a
      caller — topology setup, called once per (mesh, k).

    Corpus-dependent values flowing into a ``static_argnames`` position
    of a module-level jit entry are flagged the same way (every distinct
    value is a fresh executable).

(b) **transfer hygiene** — inside the hot serving cone (searcher
    dispatch, pipeline dispatch/fetch stages, tiering upload ring, mesh
    scatter paths; closed under the package call graph), implicit-sync
    operations on device-array-typed values are findings: ``float()`` /
    ``int()`` / ``bool()`` / truthiness, ``.item()``, ``np.asarray`` /
    ``np.array``, ``jax.device_get``.  Device-ness is tracked from
    ``jnp.*`` results, calls to known jit entries, and dataclass
    attributes annotated ``jax.Array`` (``SegmentedSnapshot.n_docs``
    caught exactly the per-dispatch sync this PR fixed).  d2h is
    confined to the fetch stage by construction: ``ops.topk
    .fetch_packed`` / ``unpack_topk`` are the named exemption, and
    every OTHER d2h site must carry a reviewed allowlist reason —
    :func:`explained_transfer_sites` hands that same set to the runtime
    device witness, so an observation the static cone didn't explain
    fails the instrumented run.

(c) **donation audit** — a call into a jit seam (a function holding a
    jit creation, or a module-level jit entry) whose array argument is
    provably dead after the call (the same name/attr — or an enclosing
    attr — is rebound later in the caller) without ``donate_argnums``
    is a finding-for-review: donation would let XLA reuse the buffer
    in-place on TPU, but aliasing (published snapshots holding the old
    array) can make it unsound, so each site is reviewed and either
    fixed or pinned with the reason.

Like every graftcheck pass: pure stdlib AST, may-miss on unresolvable
calls, stable line-number-free keys, committed allowlist carries one
reviewed reason per intentional finding.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from tools.graftcheck.core import (Finding, FuncInfo, ModuleInfo,
                                   SourceTree, _dotted)
from tools.graftcheck.jitpurity import (_SHARD_MAP_NAMES, _Purity,
                                        _is_jit_expr)

# corpus-dependent attribute leaves: values that grow with the indexed
# corpus (doc counts, nnz, live totals) — capacity-class attrs
# (`_doc_cap`, `_chunk`, `_min_cap`) are deliberately NOT here
CORPUS_ATTRS = {
    "n_docs", "num_docs", "nnz", "num_names", "n_names", "n_live",
    "doc_count", "total_docs", "nnz_live", "live_total", "vocab_size",
}

# the capacity-class sanitizer: power-of-two bucketing caps the number
# of distinct cache keys at O(log corpus)
SANITIZERS = {"next_capacity"}

# the hot serving cone roots (ISSUE 19): searcher dispatch, pipeline
# dispatch/fetch stages, tiering upload ring, mesh scatter paths.  A
# missing root whose module still exists is a finding — a rename must
# update this list, not silently shrink the cone.  `df_host` is a root
# of its own because it is reached from the tiered dispatch via
# PROPERTY access, which call resolution cannot follow; the runtime
# witness surfaced it (see the allowlist reason on its finding).
CONE_ROOTS = (
    "engine.searcher.Searcher._dispatch_chunk",
    "engine.searcher.Searcher._dispatch_tiered",
    "engine.searcher.SearchLoop._search_chunks",
    "engine.searcher.Searcher._search_unbounded",
    "engine.segments.SegmentedSnapshot.df_host",
    "engine.searcher.SearchLoop._run_pipelined",
    "engine.searcher.SearchLoop._run_inline",
    "engine.pipeline.PipelineExecutor._dispatch_loop",
    "engine.pipeline.PipelineExecutor._fetch_loop",
    "engine.tiering.TierManager.prefetch",
    "engine.tiering.TierManager.fault_in",
    "engine.tiering.TierManager.handle_view",
    "engine.tiering.TierManager._build_device",
    "engine.dense.EmbeddingColumn.search_batch",
    "parallel.mesh_index.MeshSearcher._dispatch_chunk",
    "parallel.mesh_index.MeshSearcher._step",
    "parallel.mesh_index.MeshSearcher._rank_all",
)

# d2h lives HERE by construction (PR 3): the pipeline's named fetch
# stage and its host-side inverse.  (module, function-leaf) pairs —
# the same naming the runtime witness derives from frames.
FETCH_STAGE = {("ops.topk", "fetch_packed"), ("ops.topk", "unpack_topk")}

# sanctioned bulk-transfer stages OUTSIDE the serving cone: checkpoint
# export fetches every device buffer to host by definition (that IS the
# operation), and runs off the serving path under the write lock.
# Named here so the runtime witness can explain their transfers without
# dragging checkpoint code into the hot-cone analysis; a hot-path
# function must never be added to this set — put it in CONE_ROOTS and
# let the finding force a review instead.
BULK_STAGES = {
    ("engine.index", "export_snapshot_arrays"),
    ("engine.segments", "export_full_state"),
    ("engine.dense", "export_arrays"),
    # the host-fallback mirror build (ISSUE 20): fetching the snapshot
    # arrays + device-computed per-entry impacts to host IS the
    # operation (the mirror exists so a sick device can stop serving).
    # Built once per snapshot, off the device serving path.
    ("engine.compute_health", "_fetch_host"),
}

_SYNC_BUILTINS = {"float", "int", "bool"}
_NP_FETCHERS = {"asarray", "array", "ascontiguousarray"}

_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _shallow(nodes, *, through_classes: bool = False):
    """Walk ``nodes`` and their descendants without descending into
    nested function/lambda scopes (and, by default, class bodies)."""
    stack = list(nodes)
    while stack:
        n = stack.pop()
        yield n
        for c in ast.iter_child_nodes(n):
            if isinstance(c, _SCOPES):
                continue
            if isinstance(c, ast.ClassDef) and not through_classes:
                continue
            stack.append(c)


def _body_of(fi: FuncInfo) -> list:
    body = fi.node.body
    if not isinstance(body, list):          # Lambda
        body = [ast.Expr(value=body)]
    return body


# ---------------------------------------------------------------------------
# jit root discovery (extends the jitpurity finder with scope + kwargs)
# ---------------------------------------------------------------------------

@dataclass
class JitRoot:
    mi: ModuleInfo
    fi: FuncInfo | None       # the jitted callable, when resolvable
    label: str
    kind: str                 # "jit" | "shard_map"
    call: ast.Call | None     # jit()/shard_map()/partial() call node
    scope: FuncInfo | None    # enclosing function (None = module scope)
    bound: str | None         # module-level name the entry is bound to
    static_names: tuple       # static_argnames of the jit call
    donated: bool             # donate_argnums/donate_argnames present
    lineno: int


def _jit_kwargs(call: ast.Call | None) -> tuple[tuple, bool]:
    """(static_argnames, donated) from a jit/partial(jit, …) call."""
    if call is None:
        return (), False
    kws = list(call.keywords)
    # partial(jax.jit, …)(f): kwargs may sit on the inner partial call
    if isinstance(call.func, ast.Call):
        kws += list(call.func.keywords)
    static: list[str] = []
    donated = False
    for kw in kws:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            donated = True
        if kw.arg == "static_argnames":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    static.append(e.value)
    return tuple(static), donated


def _all_funcs(mi: ModuleInfo) -> list[FuncInfo]:
    out: list[FuncInfo] = []

    def rec(fi: FuncInfo) -> None:
        out.append(fi)
        for c in fi.nested.values():
            rec(c)
    for fi in mi.functions.values():
        rec(fi)
    for ci in mi.classes.values():
        for fi in ci.methods.values():
            rec(fi)
    return out


def jit_roots(tree: SourceTree) -> list[JitRoot]:
    """Every jit/shard_map entry in the package, with its creation
    scope, binding, static argnames, and donation flag."""
    purity = _Purity(tree)
    out: list[JitRoot] = []
    for mi in tree.modules.values():
        by_name = purity._funcs_by_name(mi)
        scopes: list[tuple[FuncInfo | None, list]] = [
            (None, list(mi.tree.body))]
        scopes += [(fi, _body_of(fi)) for fi in _all_funcs(mi)]
        for scope, body in scopes:
            for node in _shallow(body, through_classes=scope is None):
                # decorated defs belong to the scope holding the def
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    continue
                if isinstance(node, ast.Call):
                    d = _dotted(node.func)
                    leaf = d.split(".")[-1] if d else ""
                    is_jit = _is_jit_expr(node.func) or (
                        _is_jit_expr(node) and not node.args)
                    is_smap = leaf in _SHARD_MAP_NAMES
                    if not ((is_jit or is_smap) and node.args):
                        continue
                    arg = node.args[0]
                    kind = "shard_map" if is_smap else "jit"
                    static, donated = _jit_kwargs(node)
                    fi = None
                    if isinstance(arg, ast.Name):
                        fi = by_name.get(arg.id)
                        name = arg.id
                    elif isinstance(arg, ast.Lambda):
                        fi = FuncInfo(
                            f"{mi.name}.<lambda@L{arg.lineno}>",
                            mi.name, None, arg)
                        name = fi.qual
                    else:
                        name = _dotted(arg) or f"<expr@L{arg.lineno}>"
                    bound = None
                    if scope is None:
                        for stmt in mi.tree.body:
                            if isinstance(stmt, ast.Assign) \
                                    and stmt.value is node:
                                for t in stmt.targets:
                                    if isinstance(t, ast.Name):
                                        bound = t.id
                    out.append(JitRoot(
                        mi, fi, f"{kind}({name})", kind, node, scope,
                        bound, static, donated, node.lineno))
        # decorator roots: scope = where the def itself lives
        parent_scope: dict[int, FuncInfo | None] = {}
        for fi in _all_funcs(mi):
            parent_scope[id(fi.node)] = fi.parent
        for fi in _all_funcs(mi):
            for dec in fi.node.decorator_list:
                if _is_jit_expr(dec):
                    call = dec if isinstance(dec, ast.Call) else None
                    static, donated = _jit_kwargs(call)
                    bound = (fi.node.name
                             if fi.parent is None and fi.cls is None
                             else None)
                    out.append(JitRoot(
                        mi, fi, f"@jit {fi.qual}", "jit", call,
                        parent_scope.get(id(fi.node)), bound, static,
                        donated, fi.node.lineno))
    return out


# ---------------------------------------------------------------------------
# corpus-value taint (pass a) — wallclock-style name chaining
# ---------------------------------------------------------------------------

def _corpus_tainted(expr: ast.expr, tainted: set[str]) -> bool:
    """True if ``expr`` may carry a corpus-dependent value that has not
    passed through a capacity-class sanitizer."""
    if isinstance(expr, ast.Call):
        d = _dotted(expr.func) or ""
        leaf = d.split(".")[-1]
        if leaf in SANITIZERS:
            return False                    # bucketed: capacity-class
        if leaf in ("min", "max"):
            # bounded by any clean operand: at most O(bound) distinct
            # values, stabilizing once the corpus outgrows it
            args = list(expr.args)
            if args and any(not _corpus_tainted(a, tainted)
                            for a in args):
                return False
            return any(_corpus_tainted(a, tainted) for a in args)
        if leaf == "len":
            return True
        if leaf in ("int", "float", "abs", "round"):
            return any(_corpus_tainted(a, tainted) for a in expr.args)
        return False                        # unresolved call: may-miss
    if isinstance(expr, ast.Attribute):
        if expr.attr in CORPUS_ATTRS or expr.attr in ("shape", "size",
                                                      "nbytes"):
            return True
        return False
    if isinstance(expr, ast.Name):
        return expr.id in tainted
    if isinstance(expr, ast.Subscript):
        return _corpus_tainted(expr.value, tainted) or \
            _corpus_tainted(expr.slice, tainted)
    if isinstance(expr, (ast.BinOp, ast.BoolOp, ast.UnaryOp, ast.IfExp,
                         ast.Tuple, ast.Compare)):
        return any(_corpus_tainted(c, tainted)
                   for c in ast.iter_child_nodes(expr)
                   if isinstance(c, ast.expr))
    return False


def _corpus_taint_map(fi: FuncInfo) -> set[str]:
    """Names in ``fi`` carrying unsanitized corpus-dependent values —
    a forward pass over the (shallow) assignments, chained like the
    wallclock analyzer chains deadline arithmetic."""
    tainted: set[str] = set()
    stmts = [n for n in _shallow(_body_of(fi))
             if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign))]
    stmts.sort(key=lambda n: n.lineno)
    for _ in range(2):                      # cheap fixpoint for loops
        for stmt in stmts:
            value = stmt.value
            if value is None:
                continue
            targets = (stmt.targets if isinstance(stmt, ast.Assign)
                       else [stmt.target])
            hit = _corpus_tainted(value, tainted)
            for t in targets:
                names = ([t.id] if isinstance(t, ast.Name) else
                         [e.id for e in getattr(t, "elts", [])
                          if isinstance(e, ast.Name)])
                for n in names:
                    if hit:
                        tainted.add(n)
                    else:
                        tainted.discard(n)  # re-bound clean (min/
                        # next_capacity over a previously raw value)
    return tainted


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------

class _DeviceCheck:
    def __init__(self, tree: SourceTree,
                 cone_roots: tuple = CONE_ROOTS) -> None:
        self.tree = tree
        self.cone_roots = cone_roots
        self.findings: list[Finding] = []
        purity = _Purity(tree)
        self._lg = purity._lg
        self.roots = jit_roots(tree)
        self._root_fis = {id(r.fi) for r in self.roots
                          if r.fi is not None}
        # module-level jit entries: "module.bound" -> JitRoot
        self.entries: dict[str, JitRoot] = {
            f"{r.mi.name}.{r.bound}": r
            for r in self.roots if r.bound is not None}
        self._device_attrs = self._collect_device_attrs()

    # -- shared helpers ---------------------------------------------------

    def _flag(self, mi: ModuleInfo, key: str, msg: str,
              node: ast.AST) -> None:
        if any(f.key == key for f in self.findings):
            return
        self.findings.append(Finding(
            "devicecheck", key, msg, mi.relpath,
            getattr(node, "lineno", 0)))

    def _entry_of_call(self, mi: ModuleInfo,
                       node: ast.Call) -> JitRoot | None:
        """Resolve a call to a module-level jit entry (same module or
        through imports)."""
        d = _dotted(node.func)
        if d is None:
            return None
        r = self.entries.get(f"{mi.name}.{d}")
        if r is not None:
            return r
        head = d.split(".")[0]
        full = mi.imports.get(head)
        if full is None:
            return None
        full = full + d[len(head):]
        if not full.startswith(self.tree.package + "."):
            return None
        return self.entries.get(full[len(self.tree.package) + 1:])

    def _collect_device_attrs(self) -> dict[str, set[str]]:
        """class qual -> attrs annotated as device arrays (``jax.Array``
        / ``jnp.ndarray`` dataclass fields)."""
        out: dict[str, set[str]] = {}
        for mi in self.tree.modules.values():
            for ci in mi.classes.values():
                for stmt in ci.node.body:
                    if not isinstance(stmt, ast.AnnAssign) or \
                            not isinstance(stmt.target, ast.Name):
                        continue
                    ann = _dotted(stmt.annotation) or ""
                    head = ann.split(".")[0]
                    leaf = ann.split(".")[-1]
                    if head in ("jax", "jnp") and leaf in ("Array",
                                                           "ndarray"):
                        out.setdefault(ci.qual, set()).add(
                            stmt.target.id)
        return out

    # -- pass a: jit-cache discipline -------------------------------------

    def check_cache_discipline(self) -> None:
        for r in self.roots:
            if r.scope is None:
                continue                    # compiled once at import
            self._check_scoped_root(r)
        self._check_static_args()

    def _check_scoped_root(self, r: JitRoot) -> None:
        scope = r.scope
        body = _body_of(scope)
        # trace-time creation: the enclosing function is itself jitted
        if id(scope) in self._root_fis:
            return
        # lru_cache-decorated factory
        for dec in scope.node.decorator_list:
            d = _dotted(dec if not isinstance(dec, ast.Call)
                        else dec.func) or ""
            if d.split(".")[-1] in ("lru_cache", "cache"):
                return
        created = {None}                    # local names bound to the jit
        names: set[str] = set()
        stmts = [n for n in _shallow(body)]
        for n in stmts:
            if isinstance(n, ast.Assign) and n.value is r.call:
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
        created = names
        # memo-store: container[key] = <jit or its name>
        for n in stmts:
            key_expr = None
            if isinstance(n, ast.Assign) and any(
                    isinstance(t, ast.Subscript) for t in n.targets):
                v = n.value
                if v is r.call or (isinstance(v, ast.Name)
                                   and v.id in created):
                    for t in n.targets:
                        if isinstance(t, ast.Subscript):
                            key_expr = t.slice
            elif isinstance(n, ast.Call) and isinstance(
                    n.func, ast.Attribute) and n.func.attr == \
                    "setdefault" and len(n.args) == 2:
                v = n.args[1]
                if v is r.call or (isinstance(v, ast.Name)
                                   and v.id in created):
                    key_expr = n.args[0]
            if key_expr is not None:
                tainted = _corpus_taint_map(scope)
                if _corpus_tainted(key_expr, tainted):
                    self._flag(
                        r.mi,
                        f"devicecheck:jit-unstable-key:{scope.qual}",
                        f"jit cache in {scope.qual} is keyed on a "
                        f"corpus-dependent value ({r.label}): every "
                        f"corpus size mints a fresh executable — key "
                        f"on next_capacity()-bucketed values only",
                        key_expr)
                return                      # seam found
        # factory: the jit (or a nested jitted def) escapes via return
        for n in stmts:
            if not isinstance(n, ast.Return) or n.value is None:
                continue
            v = n.value
            if v is r.call or _is_jit_expr(getattr(v, "func", v)):
                return
            if isinstance(v, ast.Name):
                if v.id in created:
                    return
                nested = scope.nested.get(v.id)
                if nested is not None and id(nested) in self._root_fis:
                    return
        self._flag(
            r.mi, f"devicecheck:jit-uncached:{scope.qual}",
            f"{r.label} is created inside {scope.qual} without a "
            f"memoized cache seam (no subscripted store, lru_cache, "
            f"factory return, or enclosing jit): every call re-traces "
            f"and re-compiles",
            r.call if r.call is not None else scope.node)

    def _check_static_args(self) -> None:
        """Corpus-dependent values flowing into ``static_argnames``
        positions of module-level jit entries."""
        for mi in self.tree.modules.values():
            for fi in _all_funcs(mi):
                tainted = None
                for node in _shallow(_body_of(fi)):
                    if not isinstance(node, ast.Call):
                        continue
                    entry = self._entry_of_call(mi, node)
                    if entry is None or not entry.static_names:
                        continue
                    for kw in node.keywords:
                        if kw.arg not in entry.static_names:
                            continue
                        if tainted is None:
                            tainted = _corpus_taint_map(fi)
                        if _corpus_tainted(kw.value, tainted):
                            self._flag(
                                mi,
                                f"devicecheck:jit-corpus-static:"
                                f"{fi.qual}:{entry.bound}.{kw.arg}",
                                f"{fi.qual} passes a corpus-dependent "
                                f"value as static arg `{kw.arg}` of "
                                f"jit entry {entry.mi.name}."
                                f"{entry.bound}: every distinct value "
                                f"compiles a fresh executable",
                                kw.value)

    # -- pass b: transfer hygiene -----------------------------------------

    def _resolve_root(self, qual: str) -> tuple[ModuleInfo,
                                                FuncInfo] | None:
        modname, _, leaf = qual.rpartition(".")
        while modname:
            mi = self.tree.modules.get(modname)
            if mi is not None:
                rest = qual[len(modname) + 1:].split(".")
                if len(rest) == 2 and rest[0] in mi.classes:
                    fi = mi.classes[rest[0]].methods.get(rest[1])
                elif len(rest) == 1:
                    fi = mi.functions.get(rest[0])
                else:
                    fi = None
                if fi is not None:
                    return mi, fi
                return None
            modname, _, _ = modname.rpartition(".")
        return None

    def cone(self) -> dict[str, tuple[ModuleInfo, FuncInfo]]:
        """The hot serving cone: CONE_ROOTS closed under resolvable
        package calls."""
        out: dict[str, tuple[ModuleInfo, FuncInfo]] = {}
        work: list[tuple[ModuleInfo, FuncInfo]] = []
        for qual in self.cone_roots:
            got = self._resolve_root(qual)
            if got is None:
                modname = qual.split(".")
                # a missing root is only a drift finding when its module
                # still exists (mini-trees in tests don't carry the real
                # modules; a deleted module removes its cone legitimately)
                for i in range(len(modname) - 1, 0, -1):
                    if ".".join(modname[:i]) in self.tree.modules:
                        self._flag(
                            self.tree.modules[".".join(modname[:i])],
                            f"devicecheck:cone-root-missing:{qual}",
                            f"hot-cone root {qual} no longer resolves "
                            f"— a rename must update "
                            f"devicecheck.CONE_ROOTS, not silently "
                            f"shrink the analyzed cone",
                            self.tree.modules[
                                ".".join(modname[:i])].tree)
                        break
                continue
            work.append(got)
        seen: set[str] = set()
        while work:
            mi, fi = work.pop()
            if fi.qual in seen:
                continue
            seen.add(fi.qual)
            out[fi.qual] = (mi, fi)
            locals_ = self._lg._local_types(mi, fi)
            for node in _shallow(_body_of(fi)):
                if not isinstance(node, ast.Call):
                    continue
                for target in self._lg._resolve_call(mi, fi, locals_,
                                                     node):
                    work.append((self.tree.modules[target.module],
                                 target))
        return out

    def _device_taint_map(self, mi: ModuleInfo,
                          fi: FuncInfo) -> set[str]:
        """Local names that may hold device arrays."""
        locals_ = self._lg._local_types(mi, fi)
        tainted: set[str] = set()
        stmts = [n for n in _shallow(_body_of(fi))
                 if isinstance(n, ast.Assign)]
        stmts.sort(key=lambda n: n.lineno)

        def device(expr: ast.expr) -> bool:
            if isinstance(expr, ast.Call):
                d = _dotted(expr.func) or ""
                head = d.split(".")[0]
                if head == "jnp" or d.startswith("jax.numpy.") \
                        or d == "jax.device_put":
                    return True
                if self._entry_of_call(mi, expr) is not None:
                    return True
                # annotation-driven: a package function declaring a
                # device-array return (`-> jax.Array`, tuples thereof)
                # yields device values even without a jit wrapper
                # (full_ranking is plain jnp but returns device arrays)
                for target in self._lg._resolve_call(mi, fi, locals_,
                                                     expr):
                    ret = getattr(target.node, "returns", None)
                    if ret is not None and any(
                            t in ast.unparse(ret)
                            for t in ("jax.Array", "jnp.ndarray")):
                        return True
                # a method on a device value yields a device value
                # (`scores.max()`, `.astype()`, `.at[i].add()`) —
                # `.item()`/`.tolist()` DO leave the device, but they
                # are themselves flagged as syncs, not taint carriers
                if isinstance(expr.func, ast.Attribute) and \
                        expr.func.attr not in ("item", "tolist") and \
                        device(expr.func.value):
                    return True
                return False
            if isinstance(expr, ast.Attribute):
                # annotation-driven ONLY: .shape/.dtype/host fields on
                # a device value are metadata, not transfers
                base = expr.value
                classes: set[str] = set()
                if isinstance(base, ast.Name):
                    classes = set(locals_.get(base.id, ()))
                    if base.id == "self" and fi.cls is not None:
                        classes.add(fi.cls.qual)
                return any(expr.attr in self._device_attrs.get(c, ())
                           for c in classes)
            if isinstance(expr, ast.Name):
                return expr.id in tainted
            if isinstance(expr, (ast.Subscript, ast.BinOp, ast.UnaryOp,
                                 ast.IfExp)):
                return any(device(c) for c in ast.iter_child_nodes(expr)
                           if isinstance(c, ast.expr))
            return False

        for _ in range(2):
            for stmt in stmts:
                hit = device(stmt.value)
                for t in stmt.targets:
                    names = ([t.id] if isinstance(t, ast.Name) else
                             [e.id for e in getattr(t, "elts", [])
                              if isinstance(e, ast.Name)])
                    for n in names:
                        if hit:
                            tainted.add(n)
        self._device_expr = device
        return tainted

    def check_transfers(self) -> None:
        for qual, (mi, fi) in sorted(self.cone().items()):
            self._device_taint_map(mi, fi)
            device = self._device_expr
            leaf_pair = (fi.module, qual.rsplit(".", 1)[-1])
            in_fetch = leaf_pair in FETCH_STAGE

            def flag(node, op, what):
                self._flag(
                    mi, f"devicecheck:transfer:{qual}:{op}",
                    f"implicit device sync in the hot serving cone: "
                    f"{qual} {what} — blocks dispatch until the device "
                    f"round-trip completes (d2h belongs in the fetch "
                    f"stage, ops.topk.fetch_packed)",
                    node)

            for node in _shallow(_body_of(fi)):
                if isinstance(node, ast.Call):
                    d = _dotted(node.func) or ""
                    head, leaf = (d.split(".")[0], d.split(".")[-1])
                    if d in _SYNC_BUILTINS and node.args and \
                            device(node.args[0]):
                        flag(node, d, f"calls {d}() on a device value")
                    elif head in ("np", "numpy", "onp") and \
                            leaf in _NP_FETCHERS and node.args and \
                            device(node.args[0]) and not in_fetch:
                        flag(node, "asarray",
                             f"calls {d}() on a device value")
                    elif d == "jax.device_get" and not in_fetch:
                        flag(node, "device_get", "calls jax.device_get")
                    elif isinstance(node.func, ast.Attribute) and \
                            node.func.attr == "item" and \
                            device(node.func.value):
                        flag(node, "item",
                             "calls .item() on a device value")
                if isinstance(node, (ast.If, ast.While)) and \
                        device(node.test):
                    flag(node.test, "truthiness",
                         "branches on a device value (implicit bool "
                         "sync)")

    # -- pass c: donation audit -------------------------------------------

    def check_donation(self) -> None:
        # functions whose body creates an undonated jit = donation seams
        seam_scopes: dict[int, JitRoot] = {
            id(r.scope): r for r in self.roots
            if r.scope is not None and not r.donated}
        for mi in self.tree.modules.values():
            for fi in _all_funcs(mi):
                locals_ = self._lg._local_types(mi, fi)
                stmts = list(_shallow(_body_of(fi)))
                assigns = [n for n in stmts if isinstance(n, ast.Assign)]
                for node in stmts:
                    if not isinstance(node, ast.Call):
                        continue
                    entry = self._entry_of_call(mi, node)
                    undonated = entry is not None and not entry.donated
                    callee_leaf = None
                    if entry is not None:
                        callee_leaf = entry.bound
                    else:
                        for target in self._lg._resolve_call(
                                mi, fi, locals_, node):
                            if id(target) in seam_scopes:
                                undonated = True
                                callee_leaf = target.qual.rsplit(
                                    ".", 1)[-1]
                                break
                    if not undonated:
                        continue
                    for arg in list(node.args) + [
                            kw.value for kw in node.keywords]:
                        d = _dotted(arg)
                        if d is None or d == "self":
                            continue
                        if self._dead_after(assigns, node, d):
                            self._flag(
                                mi,
                                f"devicecheck:donation:{fi.qual}:"
                                f"{callee_leaf}",
                                f"{fi.qual} passes `{d}` into jit seam "
                                f"`{callee_leaf}` and rebinds it "
                                f"afterwards — the buffer is dead "
                                f"after the call; donate_argnums "
                                f"would reuse it in place on TPU "
                                f"(review: unsound if older snapshots "
                                f"alias it)",
                                node)
                            break

    @staticmethod
    def _dead_after(assigns: list, call: ast.Call, d: str) -> bool:
        for stmt in assigns:
            if stmt.lineno <= call.lineno:
                continue
            for t in stmt.targets:
                td = _dotted(t)
                if td is None:
                    continue
                if td == d or d.startswith(td + "."):
                    return True
        return False

    # -- entry ------------------------------------------------------------

    def check(self) -> list[Finding]:
        self.check_cache_discipline()
        self.check_transfers()
        self.check_donation()
        return self.findings


def explained_transfer_sites(tree: SourceTree,
                             allowlist: dict[str, str] | None = None
                             ) -> set[tuple[str, str]]:
    """(module, function-leaf) pairs where a d2h transfer is statically
    explained: the named fetch stage, the sanctioned bulk stages
    (checkpoint export), plus every transfer finding pinned with a
    reviewed reason in the committed allowlist.  The runtime device
    witness fails on any observed transfer OUTSIDE this set — each
    side validating the other (the lockdep contract)."""
    if allowlist is None:
        from tools.graftcheck.core import load_allowlist
        allowlist = load_allowlist()
    dc = _DeviceCheck(tree)
    dc.check_transfers()
    out = set(FETCH_STAGE) | set(BULK_STAGES)
    for f in dc.findings:
        if not f.key.startswith("devicecheck:transfer:"):
            continue
        if f.key not in allowlist:
            continue
        qual = f.key.split(":")[2]
        parts = qual.split(".")
        # qual is "<module>.<Class>.<meth>" or "<module>.<func>" —
        # recover the module by longest-prefix match
        for i in range(len(parts) - 1, 0, -1):
            if ".".join(parts[:i]) in tree.modules:
                out.add((".".join(parts[:i]), parts[-1]))
                break
    return out


def analyze(tree: SourceTree) -> list[Finding]:
    return _DeviceCheck(tree).check()
