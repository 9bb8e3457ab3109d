"""Project indexing + trace-reachability for tpulint.

The analyzer is a whole-project pass, not a per-file one: rule scopes
depend on *reachability* ("can a jit trace reach this function?"),
which needs imports, the class hierarchy and a call graph across every
analyzed module.

Pipeline:

1. index every ``*.py`` file into a :class:`ModuleInfo` (import alias
   table, classes, functions — including nested defs);
2. resolve the class hierarchy to find ``Block``/``HybridBlock``
   subclasses (their ``forward``/``hybrid_forward`` run under
   ``jax.jit`` once hybridized — the CachedOp equivalence);
3. fixpoint over *jit wrappers*: ``jax.jit``/``pjit``/``shard_map``/
   ``pallas_call``/``lax.scan`` etc. seed the set; any analyzed
   function that passes one of its own parameters to a known wrapper
   becomes a wrapper itself (this is how ``_program_jits(raw_fn)``
   marks every ``raw_fn`` closure as a jit entry point);
4. BFS over call edges from the seeds → ``trace_reachable`` set, and a
   second BFS from per-step seeds (``Trainer.step``/``Optimizer.update``)
   → ``perstep_reachable`` set;
5. two more interprocedural passes reuse the same call graph:
   *shard-axis contexts* (which mesh axis names are bound by every
   ``shard_map``/``pmap``/``vmap(axis_name=)`` context a function is
   reachable from — TPU007's ground truth) and *thread reachability*
   (functions running on a ``threading.Thread`` target, transitively —
   TPU011/TPU012's ground truth).

The call graph is exposed as :meth:`Project.callees` /
:meth:`Project.callers` / :meth:`Project.call_sites` so rules can walk
it interprocedurally (e.g. resolving an ``axis_name`` parameter to the
string constants its analyzed callers actually pass).

Resolution is deliberately conservative in BOTH directions: bare names
only resolve within the module (or explicit imports), ``self.m()``
resolves through the declared ancestry — so host-only code (io,
recordio, tools) never gets dragged into trace scope, and trace scope
never silently loses a hop that a simple name lookup can prove.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

# ---------------------------------------------------------------------------
# data model
# ---------------------------------------------------------------------------


@dataclass
class Finding:
    code: str
    message: str
    path: str
    line: int
    col: int
    function: str = ""
    # structured payload for machine consumers (``--format json``):
    # e.g. TPU013 carries {"cycle": [...], "edges": [...]}.  NOT part
    # of key()/fingerprints — a cycle rendered from a different edge
    # sample is still the same finding.
    extra: Optional[dict] = None

    def key(self) -> Tuple[str, int, int, str]:
        return (self.path, self.line, self.col, self.code)

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col + 1}: {self.code} {self.message}"


@dataclass
class ClassInfo:
    name: str
    module: "ModuleInfo"
    node: ast.ClassDef
    bases: List[str] = field(default_factory=list)   # dotted, resolved where possible
    methods: Dict[str, "FunctionInfo"] = field(default_factory=dict)
    is_block: bool = False       # descends from Block (TPU006 scope)
    is_hybrid: bool = False      # descends from HybridBlock (forward is traced)

    @property
    def full_name(self) -> str:
        return f"{self.module.name}.{self.name}"


@dataclass
class FunctionInfo:
    module: "ModuleInfo"
    qualname: str                      # "Class.method" / "outer.inner" / "func"
    name: str
    node: ast.FunctionDef
    cls: Optional[ClassInfo] = None
    trace_reachable: bool = False
    perstep_reachable: bool = False
    is_jit_wrapper: bool = False
    trace_reason: str = ""             # why it entered trace scope (diagnostics)
    # resolved name of the wrapper that seeded this function (e.g.
    # "jax.jit", "jax.lax.scan", or a project-local wrapper's full
    # name).  TPU008 keys off this: only real COMPILE boundaries
    # (jit/pjit/pallas_call) make closure capture a bug — control-flow
    # primitives (scan/cond) and shard_map bodies share the outer
    # trace, where capturing outer tracers is normal JAX.
    seed_wrapper: Optional[str] = None
    # -- shard-axis context (TPU007) ------------------------------------
    # axis names bound by every shard_map/pmap/vmap context this
    # function is reachable from (None until some context reaches it)
    shard_axes: Optional[Set[str]] = None
    # True when at least one reaching context's axes could not be
    # extracted statically — rules must not flag then
    shard_axes_unknown: bool = False
    shard_reason: str = ""
    # -- thread context (TPU011/TPU012) ---------------------------------
    thread_entry: bool = False         # literally a Thread(target=...)
    thread_reachable: bool = False     # entry or called from one
    # params declared static at the jit boundary (static_argnums/
    # static_argnames) — host values by contract, excluded from taint
    static_params: Set[str] = field(default_factory=set)
    # statics this function forwards to jit when IT is a wrapper
    wrapper_statics: Optional[Tuple[Tuple[int, ...], Tuple[str, ...]]] = None
    # argnums donated when this function RETURNS a donating jit
    # (`return jax.jit(g, donate_argnums=(0,))`) — TPU009 tracks the
    # returned callable through local bindings at call sites
    returns_donating: Optional[Tuple[int, ...]] = None

    @property
    def full_name(self) -> str:
        return f"{self.module.name}.{self.qualname}"


@dataclass
class ModuleInfo:
    name: str                          # dotted module name
    path: str
    tree: ast.Module
    source: str
    aliases: Dict[str, str] = field(default_factory=dict)
    functions: Dict[str, FunctionInfo] = field(default_factory=dict)
    classes: Dict[str, ClassInfo] = field(default_factory=dict)


# jit entry wrappers: calling one of these with a function argument
# makes that function's body run under trace.
JIT_WRAPPERS = {
    "jax.jit", "jax.pjit",
    "jax.experimental.pjit.pjit",
    "jax.experimental.shard_map.shard_map",
    "jax.experimental.pallas.pallas_call",
    "jax.eval_shape", "jax.make_jaxpr",
    "jax.vjp", "jax.jvp", "jax.grad", "jax.value_and_grad",
    "jax.vmap", "jax.pmap",
    "jax.checkpoint", "jax.remat",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.cond",
    "jax.lax.switch", "jax.lax.fori_loop", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
}

# wrappers that additionally BIND mesh axis names for the wrapped
# function (collectives inside may name them).  `shard_map` is matched
# by resolved-name tail as well so project-local wrappers count — that
# is the cross-module propagation per-file linting could never see.
SHARD_WRAPPER_TAILS = {"shard_map", "pmap", "smap"}
AXIS_BINDING_WRAPPERS = {
    "jax.experimental.shard_map.shard_map", "jax.shard_map",
    "jax.pmap", "jax.vmap",
}

# collective ops that CONSUME an axis name (TPU007); tail names of
# jax.lax.* — matched on the resolved dotted path.
COLLECTIVE_FUNCS = {
    "jax.lax.psum", "jax.lax.pmean", "jax.lax.pmax", "jax.lax.pmin",
    "jax.lax.psum_scatter", "jax.lax.all_gather", "jax.lax.all_to_all",
    "jax.lax.axis_index", "jax.lax.axis_size", "jax.lax.ppermute",
    "jax.lax.pshuffle", "jax.lax.pswapaxes",
}

THREAD_FACTORIES = {"threading.Thread", "threading.Timer"}

# methods whose bodies run once per training step (host code, but on
# the step critical path — explicit syncs there serialize the device
# queue).  Scoped to optimizer/trainer-like classes, see _perstep_seed.
PERSTEP_METHOD_NAMES = {"step", "update", "update_multi_precision"}
PERSTEP_CLASS_HINTS = ("Trainer", "Optimizer", "Updater", "KVStore", "LRScheduler")
# free functions documented as per-iteration utilities
PERSTEP_FUNCTION_NAMES = {"clip_global_norm", "allreduce_grads"}

BLOCK_ROOT_NAMES = {"Block", "HybridBlock", "SymbolBlock"}
# only these roots put `forward` under jit (plain eager Blocks —
# dataloader transforms etc. — are host-only by design)
HYBRID_ROOT_NAMES = {"HybridBlock", "SymbolBlock"}


def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# ---------------------------------------------------------------------------
# indexing
# ---------------------------------------------------------------------------


class _Indexer(ast.NodeVisitor):
    """One pass per module: aliases, classes, functions (incl. nested)."""

    def __init__(self, mod: ModuleInfo, pkg_parts: List[str]):
        self.mod = mod
        self.pkg_parts = pkg_parts      # package path of the module, for relative imports
        self.scope: List[str] = []      # qualname parts
        self.cls_stack: List[Optional[ClassInfo]] = []

    # -- imports --------------------------------------------------------- #
    def visit_Import(self, node: ast.Import):
        for a in node.names:
            self.mod.aliases[a.asname or a.name.split(".")[0]] = (
                a.name if a.asname else a.name.split(".")[0])
            if a.asname:
                self.mod.aliases[a.asname] = a.name

    def visit_ImportFrom(self, node: ast.ImportFrom):
        if node.level:
            base_parts = self.pkg_parts[: len(self.pkg_parts) - (node.level - 1)]
            base = ".".join(base_parts + ([node.module] if node.module else []))
        else:
            base = node.module or ""
        for a in node.names:
            if a.name == "*":
                continue
            target = f"{base}.{a.name}" if base else a.name
            self.mod.aliases[a.asname or a.name] = target

    # -- defs ------------------------------------------------------------- #
    def visit_ClassDef(self, node: ast.ClassDef):
        info = ClassInfo(name=node.name, module=self.mod, node=node)
        for b in node.bases:
            d = dotted_name(b)
            if d:
                info.bases.append(d)
        self.mod.classes[node.name] = info
        self.scope.append(node.name)
        self.cls_stack.append(info)
        self.generic_visit(node)
        self.cls_stack.pop()
        self.scope.pop()

    def _visit_func(self, node):
        qual = ".".join(self.scope + [node.name])
        cls = self.cls_stack[-1] if self.cls_stack else None
        info = FunctionInfo(module=self.mod, qualname=qual, name=node.name,
                            node=node, cls=cls)
        self.mod.functions[qual] = info
        if cls is not None and len(self.scope) and self.scope[-1] == cls.name:
            cls.methods[node.name] = info
        self.scope.append(node.name)
        self.cls_stack.append(None)     # nested defs are not methods
        self.generic_visit(node)
        self.cls_stack.pop()
        self.scope.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func


# ---------------------------------------------------------------------------
# project
# ---------------------------------------------------------------------------


class Project:
    """The analyzed file set plus all derived graphs."""

    def __init__(self, paths: List[str]):
        self.modules: Dict[str, ModuleInfo] = {}
        self.errors: List[str] = []
        for f in self._collect_files(paths):
            self._index_file(f)
        self._resolve_block_classes()
        self._compute_jit_wrappers()
        self._build_call_graph()
        self._compute_reachability()
        self._compute_shard_axes()
        self._compute_thread_reachable()
        self._compute_donations()
        self._compute_registrations()

    # -- file discovery --------------------------------------------------- #
    @staticmethod
    def _collect_files(paths: List[str]) -> List[str]:
        out: List[str] = []
        for p in paths:
            if os.path.isdir(p):
                for root, dirs, files in os.walk(p):
                    dirs[:] = sorted(d for d in dirs
                                     if d not in ("__pycache__", ".git"))
                    for fn in sorted(files):
                        if fn.endswith(".py"):
                            out.append(os.path.join(root, fn))
            elif p.endswith(".py"):
                out.append(p)
        return out

    @staticmethod
    def _module_name(path: str) -> Tuple[str, List[str]]:
        """Dotted module name from the filesystem (walk up __init__.py)."""
        ap = os.path.abspath(path)
        parts = [os.path.splitext(os.path.basename(ap))[0]]
        d = os.path.dirname(ap)
        while os.path.exists(os.path.join(d, "__init__.py")):
            parts.append(os.path.basename(d))
            d = os.path.dirname(d)
        parts.reverse()
        if parts[-1] == "__init__":
            parts.pop()
        name = ".".join(parts)
        pkg_parts = parts if path.endswith("__init__.py") else parts[:-1]
        return name, pkg_parts

    def _index_file(self, path: str):
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
            tree = ast.parse(src, filename=path)
        except (SyntaxError, UnicodeDecodeError) as e:
            self.errors.append(f"{path}: {e}")
            return
        name, pkg_parts = self._module_name(path)
        mod = ModuleInfo(name=name, path=path, tree=tree, source=src)
        _Indexer(mod, pkg_parts).visit(tree)
        self.modules[name] = mod

    # -- resolution helpers ----------------------------------------------- #
    def resolve(self, mod: ModuleInfo, dotted: str) -> str:
        """Expand the leading alias of a dotted path via the module's
        import table ('onp.asarray' → 'numpy.asarray')."""
        head, _, rest = dotted.partition(".")
        target = mod.aliases.get(head)
        if target is None:
            return dotted
        return f"{target}.{rest}" if rest else target

    def lookup_function(self, full: str) -> Optional[FunctionInfo]:
        """FunctionInfo for a fully resolved dotted path, if analyzed."""
        modname, _, qual = full.rpartition(".")
        while modname:
            m = self.modules.get(modname)
            if m is not None:
                return m.functions.get(qual)
            modname, _, head = modname.rpartition(".")
            qual = f"{head}.{qual}"
        return None

    def lookup_class(self, full: str) -> Optional[ClassInfo]:
        modname, _, cname = full.rpartition(".")
        m = self.modules.get(modname)
        if m is not None:
            return m.classes.get(cname)
        # re-exported through a package __init__? follow one alias hop.
        if m is None and modname:
            pkg = self.modules.get(modname) or self.modules.get(modname + ".__init__")
            if pkg is not None:
                tgt = pkg.aliases.get(cname)
                if tgt and tgt != full:
                    return self.lookup_class(tgt)
        return None

    def _class_ancestry(self, cls: ClassInfo, seen=None) -> List[ClassInfo]:
        if seen is None:
            seen = set()
        out = []
        for b in cls.bases:
            resolved = self.resolve(cls.module, b)
            cand = self.lookup_class(resolved) or cls.module.classes.get(b)
            if cand is not None and id(cand) not in seen:
                seen.add(id(cand))
                out.append(cand)
                out.extend(self._class_ancestry(cand, seen))
        return out

    # -- block subclasses -------------------------------------------------- #
    def _resolve_block_classes(self):
        changed = True
        while changed:
            changed = False
            for mod in self.modules.values():
                for cls in mod.classes.values():
                    for b in cls.bases:
                        resolved = self.resolve(mod, b)
                        tail = resolved.rpartition(".")[2]
                        base_cls = self.lookup_class(resolved) or mod.classes.get(b)
                        if not cls.is_block and (
                                tail in BLOCK_ROOT_NAMES
                                or (base_cls is not None and base_cls.is_block)):
                            cls.is_block = True
                            changed = True
                        if not cls.is_hybrid and (
                                tail in HYBRID_ROOT_NAMES
                                or (base_cls is not None and base_cls.is_hybrid)):
                            cls.is_hybrid = True
                            changed = True

    # -- jit wrapper fixpoint ---------------------------------------------- #
    def _iter_calls(self, fn: FunctionInfo):
        """Call nodes in fn's own body (nested defs excluded — they have
        their own FunctionInfo; lambdas stay with the parent)."""
        skip: Set[int] = set()
        for child in ast.walk(fn.node):
            if child is fn.node:
                continue
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(child):
                    skip.add(id(sub))
        for child in ast.walk(fn.node):
            if isinstance(child, ast.Call) and id(child) not in skip:
                yield child

    def iter_own_nodes(self, fn: FunctionInfo):
        """All AST nodes belonging to fn's own body (nested defs excluded)."""
        skip: Set[int] = set()
        for child in ast.walk(fn.node):
            if child is fn.node:
                continue
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(child):
                    skip.add(id(sub))
        for child in ast.walk(fn.node):
            if id(child) not in skip:
                yield child

    def is_jit_wrapper_call(self, mod: ModuleInfo, call: ast.Call) -> bool:
        d = dotted_name(call.func)
        if d is None:
            return False
        resolved = self.resolve(mod, d)
        if resolved in JIT_WRAPPERS:
            return True
        target = self.lookup_function(resolved)
        return target is not None and target.is_jit_wrapper

    @staticmethod
    def _call_arg_names(call: ast.Call) -> List[str]:
        names = [a.id for a in call.args if isinstance(a, ast.Name)]
        # *args forwarding counts: `_shard_map(*args, **kwargs)` passes
        # the vararg tuple through — without this, a forwarding wrapper
        # around shard_map breaks wrapper propagation and every
        # shard_map body behind it silently drops out of trace scope
        names += [a.value.id for a in call.args
                  if isinstance(a, ast.Starred) and isinstance(a.value, ast.Name)]
        names += [kw.value.id for kw in call.keywords
                  if isinstance(kw.value, ast.Name)]
        return names

    @staticmethod
    def _extract_statics(call: ast.Call) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
        """(static_argnums, static_argnames) constants from a jit call."""

        def consts(node, typ):
            if isinstance(node, ast.Constant) and isinstance(node.value, typ):
                return (node.value,)
            if isinstance(node, (ast.Tuple, ast.List)):
                return tuple(e.value for e in node.elts
                             if isinstance(e, ast.Constant)
                             and isinstance(e.value, typ))
            return ()

        nums: Tuple[int, ...] = ()
        names: Tuple[str, ...] = ()
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                nums = consts(kw.value, int)
            elif kw.arg == "static_argnames":
                names = consts(kw.value, str)
        return nums, names

    @staticmethod
    def _apply_statics(fn: "FunctionInfo",
                       nums: Tuple[int, ...], names: Tuple[str, ...]):
        pos = fn.node.args.posonlyargs + fn.node.args.args
        for i in nums:
            if 0 <= i < len(pos):
                fn.static_params.add(pos[i].arg)
        all_names = {a.arg for a in pos + fn.node.args.kwonlyargs}
        fn.static_params.update(set(names) & all_names)

    def _local_fn_aliases(self, fn: FunctionInfo) -> Dict[str, str]:
        """Local `x = some_fn` / `x = functools.partial(some_fn, ...)`
        bindings — so `pl.pallas_call(kernel, ...)` seeds the kernel def
        even when it went through a local variable or a partial."""
        out: Dict[str, str] = {}
        for node in self.iter_own_nodes(fn):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            v = node.value
            tname = None
            if isinstance(v, ast.Name):
                tname = v.id
            elif isinstance(v, ast.Call):
                d = dotted_name(v.func)
                if d is not None and self.resolve(fn.module, d) in (
                        "functools.partial", "partial") and v.args:
                    tname = dotted_name(v.args[0])
            if tname is not None:
                out[node.targets[0].id] = tname
        return out

    def _candidate_fn_args(self, fn: FunctionInfo, call: ast.Call) -> List[str]:
        """Names plausibly naming a function among a call's arguments —
        bare names plus the inner target of inline functools.partial."""
        names = self._call_arg_names(call)
        for a in list(call.args) + [kw.value for kw in call.keywords]:
            if isinstance(a, ast.Call):
                d = dotted_name(a.func)
                if d is not None and self.resolve(fn.module, d) in (
                        "functools.partial", "partial") and a.args:
                    inner = dotted_name(a.args[0])
                    if inner is not None:
                        names.append(inner)
        return names

    def _compute_jit_wrappers(self):
        """f is a jit wrapper iff it passes one of its own parameters to
        a known wrapper — transitive (`_program_jits(raw_fn)` chains)."""
        changed = True
        while changed:
            changed = False
            for mod in self.modules.values():
                for fn in mod.functions.values():
                    if fn.is_jit_wrapper:
                        continue
                    params = {a.arg for a in (fn.node.args.posonlyargs
                                              + fn.node.args.args
                                              + fn.node.args.kwonlyargs)}
                    for va in (fn.node.args.vararg, fn.node.args.kwarg):
                        if va is not None:
                            params.add(va.arg)
                    for call in self._iter_calls(fn):
                        if not self.is_jit_wrapper_call(mod, call):
                            continue
                        if any(n in params for n in self._call_arg_names(call)):
                            fn.is_jit_wrapper = True
                            fn.wrapper_statics = self._extract_statics(call)
                            changed = True
                            break

    # -- seeds + reachability ---------------------------------------------- #
    def _decorator_seeds(self, fn: FunctionInfo) -> bool:
        for dec in fn.node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            d = dotted_name(target)
            if d and self.resolve(fn.module, d) in JIT_WRAPPERS:
                if isinstance(dec, ast.Call):
                    self._apply_statics(fn, *self._extract_statics(dec))
                fn.seed_wrapper = self.resolve(fn.module, d)
                return True
            # @partial(jax.jit, ...) / @functools.partial(jax.jit, ...)
            if isinstance(dec, ast.Call) and d is not None:
                r = self.resolve(fn.module, d)
                if r in ("functools.partial", "partial") and dec.args:
                    inner = dotted_name(dec.args[0])
                    if inner and self.resolve(fn.module, inner) in JIT_WRAPPERS:
                        self._apply_statics(fn, *self._extract_statics(dec))
                        fn.seed_wrapper = self.resolve(fn.module, inner)
                        return True
        return False

    def _seed_functions(self) -> List[FunctionInfo]:
        seeds: List[FunctionInfo] = []
        for mod in self.modules.values():
            for fn in mod.functions.values():
                if fn.cls is not None and (
                        (fn.cls.is_hybrid and fn.name == "forward")
                        or (fn.cls.is_block and fn.name == "hybrid_forward")):
                    fn.trace_reason = "Block forward (runs under jit when hybridized)"
                    seeds.append(fn)
                elif self._decorator_seeds(fn):
                    fn.trace_reason = "jit-decorated"
                    seeds.append(fn)
        # functions passed (by name, local alias, or inline partial) to a
        # jit wrapper call anywhere
        for mod in self.modules.values():
            for caller in mod.functions.values():
                local_aliases = None
                for call in self._iter_calls(caller):
                    if not self.is_jit_wrapper_call(mod, call):
                        continue
                    d = dotted_name(call.func)
                    resolved_w = self.resolve(mod, d) if d else None
                    if resolved_w in JIT_WRAPPERS:
                        statics = self._extract_statics(call)
                    else:
                        wfn = self.lookup_function(resolved_w) if resolved_w else None
                        statics = (wfn.wrapper_statics or ((), ())) if wfn else ((), ())
                    if local_aliases is None:
                        local_aliases = self._local_fn_aliases(caller)
                    for n in self._candidate_fn_args(caller, call):
                        n = local_aliases.get(n, n)
                        target = (mod.functions.get(f"{caller.qualname}.{n}")
                                  or mod.functions.get(n))
                        if target is None:
                            resolved = self.resolve(mod, n)
                            target = self.lookup_function(resolved)
                        if target is not None and not target.trace_reason:
                            target.trace_reason = (
                                f"passed to jit wrapper in {caller.qualname}")
                            target.seed_wrapper = resolved_w
                            self._apply_statics(target, *statics)
                            seeds.append(target)
        return seeds

    def _perstep_seeds(self) -> List[FunctionInfo]:
        seeds = []
        for mod in self.modules.values():
            for fn in mod.functions.values():
                if fn.cls is None:
                    if fn.name in PERSTEP_FUNCTION_NAMES:
                        seeds.append(fn)
                    continue
                if fn.name not in PERSTEP_METHOD_NAMES:
                    continue
                names = [fn.cls.name] + [c.name for c in self._class_ancestry(fn.cls)]
                if any(h in n for n in names for h in PERSTEP_CLASS_HINTS):
                    seeds.append(fn)
        return seeds

    def _resolve_call_target(self, fn: FunctionInfo,
                             d: str) -> Optional[FunctionInfo]:
        """FunctionInfo a dotted callee name resolves to from inside
        `fn` (nested def / module def / import / self.method)."""
        mod = fn.module
        if "." not in d:
            # bare name: nested def, module-level def, or import
            target = (mod.functions.get(f"{fn.qualname}.{d}")
                      or mod.functions.get(d))
            if target is None:
                resolved = self.resolve(mod, d)
                if resolved != d:
                    target = self.lookup_function(resolved)
            return target
        head, _, rest = d.partition(".")
        if head == "self" and fn.cls is not None and "." not in rest:
            target = fn.cls.methods.get(rest)
            if target is None:
                for anc in self._class_ancestry(fn.cls):
                    target = anc.methods.get(rest)
                    if target is not None:
                        break
            return target
        return self.lookup_function(self.resolve(mod, d))

    def _build_call_graph(self):
        """One resolution pass over every call: forward edges (callees),
        reverse edges (callers) and the concrete call sites.  Every
        later pass (reachability, shard axes, threads, TPU007's
        axis-parameter resolution, TPU011's lock propagation) walks
        these maps instead of re-resolving."""
        self._callee_map: Dict[int, List[FunctionInfo]] = {}
        self._caller_map: Dict[int, List[FunctionInfo]] = {}
        self._site_map: Dict[int, List[Tuple[FunctionInfo, ast.Call]]] = {}
        for mod in self.modules.values():
            for fn in mod.functions.values():
                out = self._callee_map.setdefault(id(fn), [])
                for call in self._iter_calls(fn):
                    d = dotted_name(call.func)
                    if d is None:
                        continue
                    target = self._resolve_call_target(fn, d)
                    if target is None:
                        continue
                    if target not in out:
                        out.append(target)
                    callers = self._caller_map.setdefault(id(target), [])
                    if fn not in callers:
                        callers.append(fn)
                    self._site_map.setdefault(id(target), []).append((fn, call))

    def callees(self, fn: FunctionInfo) -> List[FunctionInfo]:
        return self._callee_map.get(id(fn), [])

    def callers(self, fn: FunctionInfo) -> List[FunctionInfo]:
        return self._caller_map.get(id(fn), [])

    def call_sites(self, fn: FunctionInfo) -> List[Tuple["FunctionInfo", ast.Call]]:
        """(caller, call-node) pairs for every resolved call of `fn`."""
        return self._site_map.get(id(fn), [])

    def _callees(self, fn: FunctionInfo) -> List[FunctionInfo]:
        return self._callee_map.get(id(fn), [])

    def _compute_reachability(self):
        seeds = self._seed_functions()
        work = list(seeds)
        for fn in work:
            fn.trace_reachable = True
        while work:
            fn = work.pop()
            for callee in self._callees(fn):
                if not callee.trace_reachable:
                    callee.trace_reachable = True
                    callee.trace_reason = callee.trace_reason or (
                        f"called from {fn.full_name}")
                    work.append(callee)
        work = self._perstep_seeds()
        for fn in work:
            fn.perstep_reachable = True
        while work:
            fn = work.pop()
            for callee in self._callees(fn):
                if not callee.perstep_reachable and not callee.trace_reachable:
                    callee.perstep_reachable = True
                    work.append(callee)

    # -- shard-axis contexts (TPU007) --------------------------------------- #
    def is_shard_binding_call(self, mod: ModuleInfo, call: ast.Call) -> Optional[str]:
        """'shard' / 'pmap' / 'vmap' when this call binds mesh axis
        names for its function argument, else None.  Matched on the
        resolved tail so project-local shard_map compat shims count."""
        d = dotted_name(call.func)
        if d is None:
            return None
        resolved = self.resolve(mod, d)
        tail = resolved.rpartition(".")[2]
        if resolved in ("jax.pmap", "jax.vmap"):
            return "pmap" if resolved == "jax.pmap" else "vmap"
        if resolved in AXIS_BINDING_WRAPPERS or tail in SHARD_WRAPPER_TAILS:
            return "shard"
        return None

    def _shard_call_axes(self, caller: FunctionInfo, call: ast.Call,
                         kind: str) -> Set[str]:
        """Axis-name string constants a shard-wrapper call site binds.

        For shard_map every string constant in the call is collected
        (P(...) specs, axis_names=, partial-bound axis kwargs), plus —
        through one level of local single-assignment resolution — the
        strings behind spec/mesh variables (`in_specs = (P("data"),)`,
        `mesh = Mesh(devs, ("data", "model"))`).  Over-collection only
        widens the bound set (false-negative direction); an EMPTY
        result marks the context unextractable and disables TPU007
        along everything it reaches.

        The mesh argument is the gate: a mesh binds EVERY axis of the
        device grid, not just the ones the in/out specs name, so when
        the mesh expression doesn't resolve to a visible
        ``Mesh(..., ("a", "b"))`` construction (it usually arrives as a
        function parameter), the bound set is unknowable and the whole
        context poisons to unknown."""
        if kind in ("pmap", "vmap"):
            out: Set[str] = set()
            for kw in call.keywords:
                if kw.arg == "axis_name":
                    for sub in ast.walk(kw.value):
                        if isinstance(sub, ast.Constant) \
                                and isinstance(sub.value, str):
                            out.add(sub.value)
            return out
        local_assigns: Dict[str, ast.AST] = {}
        for node in self.iter_own_nodes(caller):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                local_assigns[node.targets[0].id] = node.value

        out = set()

        def collect(node: ast.AST, depth: int):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                    out.add(sub.value)
                elif isinstance(sub, ast.Name) and depth < 2:
                    v = local_assigns.get(sub.id)
                    if v is not None and v is not node:
                        collect(v, depth + 1)

        # locate the mesh expression: kwarg, or shard_map's 2nd
        # positional; resolve one local-assign hop
        mesh_expr: Optional[ast.AST] = None
        for kw in call.keywords:
            if kw.arg == "mesh":
                mesh_expr = kw.value
        if mesh_expr is None and len(call.args) > 1:
            mesh_expr = call.args[1]
        if isinstance(mesh_expr, ast.Name):
            mesh_expr = local_assigns.get(mesh_expr.id, mesh_expr)
        mesh_visible = mesh_expr is not None and any(
            isinstance(sub, ast.Call)
            and (dotted_name(sub.func) or "").rpartition(".")[2]
            in ("Mesh", "AbstractMesh", "make_mesh")
            for sub in ast.walk(mesh_expr))
        if not mesh_visible:
            return set()       # unknowable axis set → poison to unknown

        collect(call, 0)
        return out

    def _compute_shard_axes(self):
        """Seed the functions passed to axis-binding wrappers with the
        axes their call sites bind, then propagate through the call
        graph (union at joins — an axis bound by ANY reaching context
        is never flagged, the conservative direction for TPU007)."""
        work: List[FunctionInfo] = []

        def merge(fn: FunctionInfo, axes: Set[str], unknown: bool,
                  reason: str) -> None:
            changed = False
            if fn.shard_axes is None:
                fn.shard_axes = set(axes)
                fn.shard_reason = reason
                changed = True
            elif not axes <= fn.shard_axes:
                fn.shard_axes |= axes
                changed = True
            if unknown and not fn.shard_axes_unknown:
                fn.shard_axes_unknown = True
                changed = True
            if changed:
                work.append(fn)

        for mod in self.modules.values():
            for caller in mod.functions.values():
                local_aliases = None
                for call in self._iter_calls(caller):
                    kind = self.is_shard_binding_call(mod, call)
                    if kind is None:
                        continue
                    axes = self._shard_call_axes(caller, call, kind)
                    if local_aliases is None:
                        local_aliases = self._local_fn_aliases(caller)
                    for n in self._candidate_fn_args(caller, call):
                        n = local_aliases.get(n, n)
                        target = self._resolve_call_target(caller, n)
                        if target is not None:
                            merge(target, axes, not axes,
                                  f"wrapped by {kind} in {caller.qualname}")
        while work:
            fn = work.pop()
            for callee in self.callees(fn):
                merge(callee, fn.shard_axes or set(),
                      fn.shard_axes_unknown,
                      callee.shard_reason or f"called from {fn.full_name}")

    # -- thread reachability (TPU011/TPU012) -------------------------------- #
    def thread_target_of(self, fn: FunctionInfo,
                         call: ast.Call) -> Optional[FunctionInfo]:
        """The analyzed function a `threading.Thread(target=...)` call
        names, if this call is a thread construction."""
        d = dotted_name(call.func)
        if d is None:
            return None
        resolved = self.resolve(fn.module, d)
        if resolved not in THREAD_FACTORIES:
            return None
        for kw in call.keywords:
            if kw.arg == "target":
                t = dotted_name(kw.value)
                if t is None:
                    return None
                return self._resolve_call_target(fn, t)
        return None

    def _compute_thread_reachable(self):
        work: List[FunctionInfo] = []
        for mod in self.modules.values():
            for fn in mod.functions.values():
                for call in self._iter_calls(fn):
                    target = self.thread_target_of(fn, call)
                    if target is not None and not target.thread_entry:
                        target.thread_entry = True
                        work.append(target)
        for fn in work:
            fn.thread_reachable = True
        while work:
            fn = work.pop()
            for callee in self.callees(fn):
                if not callee.thread_reachable:
                    callee.thread_reachable = True
                    work.append(callee)

    # -- donation records (TPU009) ------------------------------------------ #
    def donating_jit_nums(self, mod: ModuleInfo,
                          node: ast.AST) -> Optional[Tuple[int, ...]]:
        """Constant donate_argnums of a `jax.jit(...)` expression, or
        None when `node` is not a donating jit / the nums aren't
        literal (dynamic donation lists are skipped, conservatively)."""
        if not isinstance(node, ast.Call):
            return None
        d = dotted_name(node.func)
        if d is None or self.resolve(mod, d) not in JIT_WRAPPERS:
            return None
        for kw in node.keywords:
            if kw.arg in ("donate_argnums", "donate_argnames"):
                if kw.arg == "donate_argnames":
                    return None      # name-keyed donation: positions unknown
                v = kw.value
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    return (v.value,)
                if isinstance(v, (ast.Tuple, ast.List)) and all(
                        isinstance(e, ast.Constant)
                        and isinstance(e.value, int) for e in v.elts):
                    return tuple(e.value for e in v.elts)
                return None
        return None

    def _compute_donations(self):
        """Record donation carriers TPU009 tracks interprocedurally:
        functions whose return value is a donating jit, and class
        attributes holding one (`self._fn = jax.jit(..., donate_argnums=)`
        in one method, called from another)."""
        self.donating_attrs: Dict[Tuple[int, str], Tuple[int, ...]] = {}
        for mod in self.modules.values():
            for fn in mod.functions.values():
                for node in self.iter_own_nodes(fn):
                    if isinstance(node, ast.Return) and node.value is not None:
                        vals = node.value.elts if isinstance(
                            node.value, ast.Tuple) else [node.value]
                        for i, v in enumerate(vals):
                            nums = self.donating_jit_nums(mod, v)
                            if nums is not None and i == 0:
                                fn.returns_donating = nums
                    elif isinstance(node, ast.Assign) and fn.cls is not None:
                        nums = self.donating_jit_nums(mod, node.value)
                        if nums is None:
                            continue
                        for tgt in node.targets:
                            if isinstance(tgt, ast.Attribute) \
                                    and isinstance(tgt.value, ast.Name) \
                                    and tgt.value.id == "self":
                                self.donating_attrs[
                                    (id(fn.cls), tgt.attr)] = nums

    # -- handler registrations (TPU013/TPU015/TPU016) ------------------------ #
    def _compute_registrations(self):
        """Registration-based call facts the lock pass consumes:

        * ``signal_handlers`` — functions installed via
          ``signal.signal(sig, handler)``;
        * ``section_callbacks`` — functions registered through a
          ``register_section(name, fn)``-style hook (the flight
          recorder's dump contributors);
        * ``section_dispatchers`` — functions in the module DEFINING
          ``register_section`` that read its registry dict and call an
          element (``for name, fn in _sections.items(): fn()``) — the
          statically-invisible indirect call the lock pass turns into
          dispatcher→callback edges.

        Kept OUT of the main call graph on purpose: registration edges
        are lock-pass facts, and splicing them into ``callees()`` would
        silently widen trace/thread reachability for every other rule.
        """
        self.signal_handlers: List[FunctionInfo] = []
        self.section_callbacks: List[FunctionInfo] = []
        self.section_dispatchers: List[FunctionInfo] = []

        def resolve_fn_arg(fn: FunctionInfo, node: ast.AST
                           ) -> Optional[FunctionInfo]:
            d = dotted_name(node)
            if d is None:
                return None
            return self._resolve_call_target(fn, d)

        # the registry dict `register_section` stores into, per module
        registry_names: Dict[str, str] = {}
        for mod in self.modules.values():
            reg = mod.functions.get("register_section")
            if reg is None:
                continue
            for node in self.iter_own_nodes(reg):
                if isinstance(node, ast.Assign) \
                        and isinstance(node.targets[0], ast.Subscript) \
                        and isinstance(node.targets[0].value, ast.Name):
                    registry_names[mod.name] = node.targets[0].value.id

        for mod in self.modules.values():
            for fn in mod.functions.values():
                for call in self._iter_calls(fn):
                    d = dotted_name(call.func)
                    if d is None:
                        continue
                    resolved = self.resolve(mod, d)
                    tail = resolved.rpartition(".")[2]
                    if resolved == "signal.signal" and len(call.args) >= 2:
                        target = resolve_fn_arg(fn, call.args[1])
                        if target is not None \
                                and target not in self.signal_handlers:
                            self.signal_handlers.append(target)
                    elif tail == "register_section" and len(call.args) >= 2:
                        target = resolve_fn_arg(fn, call.args[1])
                        if target is not None \
                                and target not in self.section_callbacks:
                            self.section_callbacks.append(target)
        for modname, regname in registry_names.items():
            mod = self.modules[modname]
            for fn in mod.functions.values():
                if fn.name == "register_section":
                    continue
                reads_registry = any(
                    isinstance(n, ast.Name) and n.id == regname
                    for n in self.iter_own_nodes(fn))
                calls_bare = any(
                    isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                    and n.func.id not in mod.aliases
                    and mod.functions.get(n.func.id) is None
                    for n in self.iter_own_nodes(fn))
                if reads_registry and calls_bare \
                        and fn not in self.section_dispatchers:
                    self.section_dispatchers.append(fn)

    # -- public ------------------------------------------------------------ #
    def iter_functions(self):
        for mod in self.modules.values():
            for fn in mod.functions.values():
                yield fn

    def trace_reachable_functions(self) -> List[FunctionInfo]:
        return [f for f in self.iter_functions() if f.trace_reachable]
