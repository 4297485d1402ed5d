"""The repo-specific rules (R001–R008).

Each rule encodes an invariant that was learned by debugging and until
now lived only in DESIGN.md prose — the docstrings cite where.  All
checks are pure AST (no jax import): they catch the *shape* of each
hazard, and the handful of sanctioned escape hatches either live in
whitelisted locations or carry an explicit
``# repro-lint: disable=RXXX`` comment at the call site, which is the
point — the exception becomes reviewable instead of ambient.
"""
from __future__ import annotations

import ast

from tools.lint.core import ENGINE_NAMES, FileContext, Rule, register

# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

_SUBPROCESS_SPAWNS = frozenset({
    "subprocess.run", "subprocess.Popen", "subprocess.call",
    "subprocess.check_call", "subprocess.check_output",
})

_COLLECTIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "psum_scatter", "all_gather",
    "all_to_all", "ppermute", "pshuffle", "pbroadcast", "axis_index",
})

_REDUCTION_METHODS = frozenset({
    "sum", "max", "min", "mean", "prod", "all", "any", "argmax", "argmin",
    "astype", "reshape", "squeeze", "item",
})


def _contains_string(node: ast.AST, text: str) -> bool:
    return any(isinstance(n, ast.Constant) and n.value == text
               for n in ast.walk(node))


# --------------------------------------------------------------------------
# R001 — the TopkRewriter breaker
# --------------------------------------------------------------------------

@register
class TopkSliceRule(Rule):
    """``lax.top_k(...)[0]`` immediately sliced again breaks XLA's fast TopK.

    Provenance: PR 6.  jax lowers ``top_k`` as sort+slice and XLA's
    TopkRewriter only recognizes slices starting at column 0 — composing
    a trailing-column slice (``[:, -1]``) folds into a ``[k-1:k]`` slice,
    the pattern dies, and the line silently runs as a full O(n log n)
    sort (measured ~812µs vs ~80µs on [64, 128] — a 10x latency loss that
    shipped unnoticed until the wall-clock gate landed).  The sanctioned
    escape hatch is ``repro.kernels.ref.kth_value``, whose
    ``optimization_barrier`` pins the intact [m, k] values so the rewrite
    fires; route through it, or barrier explicitly and suppress.
    """

    id = "R001"
    title = "top_k(...)[0] sliced again (TopkRewriter breaker)"
    provenance = "PR 6; kernels/ref.py:kth_value docstring"

    def visit_Subscript(self, node: ast.Subscript, ctx: FileContext) -> None:
        inner = node.value
        if not (isinstance(inner, ast.Subscript)
                and isinstance(inner.slice, ast.Constant)
                and inner.slice.value == 0
                and isinstance(inner.value, ast.Call)):
            return
        if ctx.full_name(inner.value.func) != "jax.lax.top_k":
            return
        if ctx.path == "src/repro/kernels/ref.py":
            fn = ctx.enclosing_function(node)
            if fn is not None and fn.name == "kth_value":
                return      # the one sanctioned, barrier-guarded site
        ctx.report(self, node,
                   "subscript on lax.top_k(...)[0] folds into the sort "
                   "lowering and breaks XLA's TopkRewriter (silent full "
                   "sort, ~10x; PR 6) — route through "
                   "repro.kernels.ref.kth_value")


# --------------------------------------------------------------------------
# R002 — version-sensitive jax APIs must stay behind repro.dist.compat
# --------------------------------------------------------------------------

@register
class CompatOnlyApiRule(Rule):
    """Version-sensitive jax APIs are reachable only through dist/compat.py.

    Provenance: ROADMAP "Seed-era note" and dist/compat.py.  These APIs
    moved or changed signature across jax releases (``jax.shard_map`` and
    its ``check_vma`` spelling, the barrier's grad rule,
    ``make_array_from_process_local_data``).  The installed jax is pinned
    in pyproject.toml; every call site goes through
    :mod:`repro.dist.compat` so the next bump is a one-file fix — a direct
    use works on the author's jax and breaks on the next, and PR 1
    restored a whole package that died this way.
    """

    id = "R002"
    title = "version-shimmed jax API used outside dist/compat.py"
    provenance = "ROADMAP seed-era note; PR 1; PR 5 (compat helpers)"

    _BANNED = (
        "jax.shard_map",
        "jax.experimental.shard_map",
        "jax.make_array_from_process_local_data",
        "jax.lax.optimization_barrier",
    )

    def applies(self, ctx: FileContext) -> bool:
        return ctx.path != "src/repro/dist/compat.py"

    def _check_name(self, node: ast.AST, name: str | None,
                    ctx: FileContext) -> None:
        if name and any(name == b or name.startswith(b + ".")
                        for b in self._BANNED):
            ctx.report(self, node,
                       f"{name} is version-shimmed — import it from "
                       f"repro.dist.compat (the one jax-version funnel, "
                       f"ROADMAP seed-era note)")

    def visit_Attribute(self, node: ast.Attribute, ctx: FileContext) -> None:
        parent = ctx.parents.get(node)
        if isinstance(parent, ast.Attribute) and parent.value is node:
            return        # inner link of a longer chain: outer node reports
        self._check_name(node, ctx.full_name(node), ctx)

    def visit_Import(self, node: ast.Import, ctx: FileContext) -> None:
        for a in node.names:
            self._check_name(node, a.name, ctx)

    def visit_ImportFrom(self, node: ast.ImportFrom, ctx: FileContext) -> None:
        if node.level or not node.module:
            return
        for a in node.names:
            self._check_name(node, f"{node.module}.{a.name}", ctx)


# --------------------------------------------------------------------------
# R003 — subprocess spawns must pin JAX_PLATFORMS
# --------------------------------------------------------------------------

@register
class SubprocessPlatformPinRule(Rule):
    """Python subprocesses must pin ``JAX_PLATFORMS`` in their env.

    Provenance: ROADMAP "Seed-era note"; PR 6 satellite.  The container
    installs a TPU plugin with no TPU attached: a spawned python that
    inherits an unset ``JAX_PLATFORMS`` stalls for *minutes* in
    GCP-metadata retries during backend autodetection before falling back
    to CPU — every smoke, bench child and test subprocess pins it.  The
    check is lexical: the enclosing function (or module, for top-level
    spawns) must mention the literal ``"JAX_PLATFORMS"`` somewhere; a
    spawn whose env is assembled elsewhere should say so with a
    suppression comment.
    """

    id = "R003"
    title = "subprocess spawn without a JAX_PLATFORMS pin in scope"
    provenance = "ROADMAP seed-era note; PR 6 (pinned every tool spawn)"

    def visit_Call(self, node: ast.Call, ctx: FileContext) -> None:
        if ctx.full_name(node.func) not in _SUBPROCESS_SPAWNS:
            return
        scope = ctx.enclosing_function(node) or ctx.tree
        if _contains_string(scope, "JAX_PLATFORMS"):
            return
        ctx.report(self, node,
                   "subprocess spawn with no JAX_PLATFORMS pin in the "
                   "enclosing scope — an inherited unset value stalls "
                   "minutes in TPU-plugin autodetection (ROADMAP "
                   "seed-era note)")


# --------------------------------------------------------------------------
# R004 — host syncs inside traced bodies
# --------------------------------------------------------------------------

@register
class HostSyncInJitRule(Rule):
    """No host-synchronizing calls inside jit-traced bodies.

    Provenance: DESIGN.md §3.1/§3.3 (raw stats stay jnp scalars so lookup
    can run inside a decode jit) and the PR 6 zero-retrace contract.
    ``.item()`` / ``np.asarray`` / ``float(array_expr)`` inside a traced
    body either crashes on tracers (when the value is data-dependent) or
    silently constant-folds trace-time state into the executable — the
    stale-capture variant of the retrace hazard R008 guards.  Host
    conversion belongs in the engine/caller layer, outside the jitted
    callee.  Heuristic: ``float()``/``int()``/``bool()`` are flagged only
    when their argument visibly involves jnp/jax or an array-reduction
    method call; static shape math (``int(x.shape[0])``) passes.
    """

    id = "R004"
    title = "host-sync call inside a jit-traced body"
    provenance = "DESIGN.md §3.3; PR 6 retrace-free hot path"

    _DIRECT = frozenset({"numpy.asarray", "numpy.array", "jax.device_get"})

    def _arrayish(self, node: ast.AST, ctx: FileContext) -> bool:
        for n in ast.walk(node):
            if isinstance(n, ast.Name) \
                    and ctx.aliases.get(n.id, "").split(".")[0] == "jax":
                return True
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                    and n.func.attr in _REDUCTION_METHODS:
                return True
        return False

    def visit_Call(self, node: ast.Call, ctx: FileContext) -> None:
        if not ctx.in_traced(node):
            return
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item" \
                and not node.args:
            ctx.report(self, node,
                       ".item() synchronizes the host inside a traced "
                       "body (DESIGN.md §3.3) — return the array and "
                       "convert outside the jit")
            return
        name = ctx.full_name(node.func)
        if name in self._DIRECT:
            ctx.report(self, node,
                       f"{name} materializes a host value inside a traced "
                       f"body — keep device values jnp until after "
                       f"dispatch (DESIGN.md §3.3)")
            return
        if isinstance(node.func, ast.Name) \
                and node.func.id in ("float", "int", "bool") \
                and len(node.args) == 1 and not node.keywords \
                and self._arrayish(node.args[0], ctx):
            ctx.report(self, node,
                       f"{node.func.id}() on an array expression inside a "
                       f"traced body forces a host sync (or crashes on "
                       f"tracers) — keep it a jnp scalar (DESIGN.md §3.3)")


# --------------------------------------------------------------------------
# R005 — the mutation surface is collective-free
# --------------------------------------------------------------------------

@register
class MutationCollectiveRule(Rule):
    """DESIGN.md §3.10: the only collective in the mutation surface is the
    id-mirror re-replication.

    Provenance: PR 9 / DESIGN.md §3.10.  Sharded online mutation scales
    because placement is a pure function of replicated host state — every
    process decides identically with ZERO placement collectives, and the
    device applies are shard-local scatters.  The one exception is
    ``replicated_row_ids`` (the host mirror rebuild at handle init and
    after reoptimize, never per-mutation).  A collective that sneaks into
    an insert/delete path turns every mutation into a cross-host
    synchronization point and silently serializes the fleet.

    Scope: all of ``core/online.py``, plus the mutation surface of
    ``core/distributed.py`` (``ShardedMutationOps`` /
    ``make_sharded_mutation``); ``replicated_row_ids`` is the whitelist.
    The search-side collectives in the same file (the §3.6/§3.7 merges)
    are out of scope by design.
    """

    id = "R005"
    title = "collective primitive in the online-mutation surface"
    provenance = "DESIGN.md §3.10; PR 9"

    _FILES = ("src/repro/core/online.py", "src/repro/core/distributed.py")
    _SURFACE = {"ShardedMutationOps", "make_sharded_mutation"}
    _WHITELIST = {"replicated_row_ids"}

    def applies(self, ctx: FileContext) -> bool:
        return ctx.path in self._FILES

    def visit_Call(self, node: ast.Call, ctx: FileContext) -> None:
        name = ctx.full_name(node.func)
        if name is None or name.split(".")[-1] not in _COLLECTIVES:
            return
        if name.split(".")[0] not in ("jax", "jax.lax"):
            return
        scopes = ctx.enclosing_scope_names(node)
        if any(s in self._WHITELIST for s in scopes):
            return
        if ctx.path.endswith("distributed.py") \
                and not any(s in self._SURFACE for s in scopes):
            return      # search-side merge collectives: out of scope
        ctx.report(self, node,
                   f"collective {name.split('.')[-1]} in the mutation "
                   f"surface — DESIGN.md §3.10 allows exactly one "
                   f"(replicated_row_ids' id-mirror re-replication); "
                   f"placement must stay a pure function of replicated "
                   f"host state")


# --------------------------------------------------------------------------
# R006 — fp64 is a build/oracle dtype, never a device-path dtype
# --------------------------------------------------------------------------

@register
class DevicePathFloat64Rule(Rule):
    """No float64 / x64 mode in device-path modules.

    Provenance: DESIGN.md §3.8 (fp64 at build, fp32 stored) and the PR 6
    x64-scoping fix: enabling global x64 broke the Pallas int32 id stores
    and pruning_power/latency stopped running at all.  fp64 belongs in
    build/oracle code (``core/pivots.py``, ``core/ref.py``, the
    ``core/online.py`` host paths); the kernels and backend inner loops
    store fp32 and accumulate f32 — the slack constants
    (``JOINT_SLACK``, ``margin``) are budgeted for exactly that, so a
    stray fp64 upcast in the device path buys no correctness and costs
    2x memory traffic plus an x64-mode footgun.
    """

    id = "R006"
    title = "float64 / enable_x64 in a device-path module"
    provenance = "DESIGN.md §3.8 dtype discipline; PR 6 x64-scoping fix"

    def applies(self, ctx: FileContext) -> bool:
        return (ctx.path.startswith("src/repro/kernels/")
                or ctx.path == "src/repro/search/backends.py")

    def visit_Attribute(self, node: ast.Attribute, ctx: FileContext) -> None:
        name = ctx.full_name(node)
        if name in ("numpy.float64", "jax.numpy.float64"):
            ctx.report(self, node,
                       f"{name} in a device-path module — fp64 is a "
                       f"build/oracle dtype (DESIGN.md §3.8); store fp32 "
                       f"and budget the slack constants")

    def visit_Constant(self, node: ast.Constant, ctx: FileContext) -> None:
        if node.value == "float64":
            ctx.report(self, node,
                       "'float64' dtype string in a device-path module "
                       "(DESIGN.md §3.8 fp64-at-build/fp32-at-store)")

    def visit_Call(self, node: ast.Call, ctx: FileContext) -> None:
        name = ctx.full_name(node.func)
        if name == "jax.config.update" and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and node.args[0].value == "jax_enable_x64":
            ctx.report(self, node,
                       "jax_enable_x64 toggled in a device-path module — "
                       "global x64 broke the Pallas int32 id stores "
                       "(PR 6); scope x64 to host/oracle code")

    def visit_Name(self, node: ast.Name, ctx: FileContext) -> None:
        if node.id == "enable_x64" or "enable_x64" in ctx.aliases.get(
                node.id, ""):
            ctx.report(self, node,
                       "enable_x64 in a device-path module (PR 6 "
                       "x64-scoping fix)")


# --------------------------------------------------------------------------
# R007 — pallas_call structural checks
# --------------------------------------------------------------------------

@register
class PallasCallStructureRule(Rule):
    """BlockSpec index_map arity must match the grid (+ scalar prefetch),
    and kernel ``*_ref`` operands must actually be read.

    Provenance: DESIGN.md §3.3/§3.9 and the PR 8 ``row_valid`` operand.
    Pallas reports an arity mismatch between an ``index_map`` lambda and
    the grid rank (plus ``num_scalar_prefetch`` leading refs) only deep
    inside tracing, long after the edit that caused it; and an operand a
    kernel accepts but never reads is how the §3.9 validity contract
    silently rots — the PR 8 kernel grew a ``row_valid`` [N, 1] operand
    precisely so tombstones mask per row, and a refactor that drops the
    read would still typecheck and still pass prefix-validity tests.
    Both checks are static here.  Grid rank is resolved from a literal
    ``grid=`` tuple (directly or via a single local assignment); sites
    with dynamic grids are skipped, not guessed.
    """

    id = "R007"
    title = "pallas_call index_map arity / unread kernel operand"
    provenance = "DESIGN.md §3.9; PR 8 row_valid operand; PR 7 cap operand"

    def visit_FunctionDef(self, node: ast.FunctionDef,
                          ctx: FileContext) -> None:
        self._check_unread_refs(node, ctx)
        self._check_index_maps(node, ctx)

    # ---- unread *_ref kernel operands
    def _check_unread_refs(self, node: ast.FunctionDef,
                           ctx: FileContext) -> None:
        args = node.args
        ref_params = [a for a in (args.posonlyargs + args.args
                                  + args.kwonlyargs)
                      if a.arg.endswith("_ref")]
        if not ref_params:
            return
        used = {n.id for stmt in node.body for n in ast.walk(stmt)
                if isinstance(n, ast.Name)}
        for a in ref_params:
            if a.arg not in used:
                ctx.report(self, a,
                           f"kernel operand {a.arg!r} is accepted but "
                           f"never read — an unread validity/bound "
                           f"operand silently voids the §3.9 masking "
                           f"contract (PR 8 row_valid)")

    # ---- index_map arity vs grid rank (+ scalar prefetch)
    def _grid_rank_and_prefetch(self, fn: ast.FunctionDef,
                                ctx: FileContext):
        rank = None
        prefetch = 0
        grid_names: dict[str, int] = {}
        for n in ast.walk(fn):
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Tuple):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        grid_names[t.id] = len(n.value.elts)
        for n in ast.walk(fn):
            if not isinstance(n, ast.Call):
                continue
            name = ctx.full_name(n.func) or ""
            is_pallas = name.endswith(".pallas_call")
            is_gridspec = name.endswith("GridSpec")
            if not (is_pallas or is_gridspec):
                continue
            for kw in n.keywords:
                if kw.arg == "grid":
                    if isinstance(kw.value, ast.Tuple):
                        rank = len(kw.value.elts)
                    elif isinstance(kw.value, ast.Name):
                        rank = grid_names.get(kw.value.id, rank)
                elif kw.arg == "num_scalar_prefetch" \
                        and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, int):
                    prefetch = kw.value.value
        return rank, prefetch

    def _check_index_maps(self, fn: ast.FunctionDef,
                          ctx: FileContext) -> None:
        has_pallas = any(
            isinstance(n, ast.Call)
            and (ctx.full_name(n.func) or "").endswith(".pallas_call")
            for n in ast.walk(fn))
        if not has_pallas:
            return
        rank, prefetch = self._grid_rank_and_prefetch(fn, ctx)
        if rank is None:
            return      # dynamic grid: skipped, not guessed
        expected = rank + prefetch
        for n in ast.walk(fn):
            if not (isinstance(n, ast.Call)
                    and (ctx.full_name(n.func) or "").endswith(".BlockSpec")):
                continue
            lam = None
            if len(n.args) >= 2 and isinstance(n.args[1], ast.Lambda):
                lam = n.args[1]
            for kw in n.keywords:
                if kw.arg == "index_map" and isinstance(kw.value, ast.Lambda):
                    lam = kw.value
            if lam is None:
                continue
            got = len(lam.args.posonlyargs) + len(lam.args.args)
            if got != expected:
                ctx.report(self, lam,
                           f"index_map takes {got} args but the grid has "
                           f"rank {rank} with {prefetch} scalar-prefetch "
                           f"operand(s) (expected {expected}) — Pallas "
                           f"only reports this deep inside tracing")


# --------------------------------------------------------------------------
# R008 — the retrace hazard
# --------------------------------------------------------------------------

@register
class RetraceHazardRule(Rule):
    """Jitted closures must not read mutable engine state at trace time.

    Provenance: DESIGN.md §3.9 and the PR 6/PR 8 dispatch-cache contract.
    The engine's hot path is ONE jitted dispatch whose cache key is
    ``(backend, k, shape, dtype, knobs, index_epoch)``; the index and
    queries flow through as *arguments*.  A fused closure that instead
    reads ``eng.index`` / ``self._tree_index`` at trace time bakes a
    stale snapshot into the executable — online mutations then silently
    search dead state (the capture variant) or force a retrace per
    mutation (the key variant), both of which the zero-retrace tests
    exist to prevent.  The rule flags attribute reads on free-variable
    engine-like names (``self`` / ``eng`` / ``engine``) inside any
    jit-traced function; capture what you need into locals *before* the
    closure (the ``note = eng._note_trace`` idiom in
    search/backends.py), or thread it through the cache key.
    """

    id = "R008"
    title = "jitted closure reads mutable engine state (retrace hazard)"
    provenance = "DESIGN.md §3.9; PR 6 dispatch cache; PR 8 index_epoch"

    def _bound_names(self, root: ast.AST) -> set[str]:
        bound: set[str] = set()
        for n in ast.walk(root):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                a = n.args
                for arg in (a.posonlyargs + a.args + a.kwonlyargs):
                    bound.add(arg.arg)
                if a.vararg:
                    bound.add(a.vararg.arg)
                if a.kwarg:
                    bound.add(a.kwarg.arg)
            elif isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                bound.add(n.id)
        return bound

    def visit_Attribute(self, node: ast.Attribute, ctx: FileContext) -> None:
        if not (isinstance(node.value, ast.Name)
                and node.value.id in ENGINE_NAMES):
            return
        # innermost traced root containing this read
        root = None
        cur = node
        while cur is not None:
            if cur in ctx.traced_functions:
                root = cur
                break
            cur = ctx.parents.get(cur)
        if root is None:
            return
        if node.value.id in self._bound_names(root):
            return      # the root's own parameter / local, not a capture
        ctx.report(self, node,
                   f"traced body reads {node.value.id}.{node.attr} — "
                   f"mutable engine state must flow through arguments or "
                   f"the dispatch-cache key (DESIGN.md §3.9; capture "
                   f"into a local before the closure like "
                   f"search/backends.py's `note = eng._note_trace`)")
