"""salint rules SAL001–SAL008: the repo's residency/kernel invariants.

Each rule encodes one invariant the paper-reproduction's correctness or
resource-accounting story depends on; ``python -m tools.salint --explain
SALxxx`` prints the rationale.  See ``docs/static_analysis.md`` for the
catalog and the suppression policy.
"""
from __future__ import annotations

import ast
import os
from typing import ClassVar, Dict, Iterator, List, Optional, Set, Tuple

from tools.salint.engine import FileContext, Rule, Violation, violation_at


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, None for anything else."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _with_item_nodes(tree: ast.AST) -> Set[int]:
    """ids of every AST node inside a ``with`` item's context expression
    (calls there are context-managed by construction)."""
    out: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                for sub in ast.walk(item.context_expr):
                    out.add(id(sub))
    return out


def _enclosing_scopes(tree: ast.AST) -> Dict[int, Tuple[Optional[str], Optional[str]]]:
    """node id -> (enclosing function name, enclosing class name)."""
    scopes: Dict[int, Tuple[Optional[str], Optional[str]]] = {}

    def visit(node: ast.AST, fn: Optional[str], cls: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            cf, cc = fn, cls
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cf = child.name
            elif isinstance(child, ast.ClassDef):
                cc = child.name
                cf = None
            scopes[id(child)] = (cf, cc)
            visit(child, cf, cc)

    scopes[id(tree)] = (None, None)
    visit(tree, None, None)
    return scopes


def _func_bodies(tree: ast.AST) -> Dict[str, ast.AST]:
    """function name -> def node, every nesting level."""
    return {
        n.name: n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }


def _calls_name(fn_node: ast.AST, names: Set[str]) -> bool:
    """True when the function body calls any attribute/name in ``names``."""
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in names:
                return True
            if isinstance(f, ast.Name) and f.id in names:
                return True
    return False


# ---------------------------------------------------------------------------
# SAL001 — kernel registry pairing (repo rule)
# ---------------------------------------------------------------------------


class Sal001KernelRegistry(Rule):
    rule_id = "SAL001"
    summary = ("every Pallas kernel module is registered in "
               "kernels/__init__.py with a reference in kernels/ref.py and "
               "swept by tests/test_kernels.py")
    rationale = (
        "The reproduction's kernel claims rest on bit-exact references: every "
        "Pallas kernel (kernels/<name>.py) must appear in KERNEL_REGISTRY "
        "(kernels/__init__.py) pairing it with its dispatch op and its "
        "oracle in kernels/ref.py, and tests/test_kernels.py must sweep the "
        "registry.  An unregistered kernel would ship without an oracle — "
        "exactly the silent drift this repo's CI is built to prevent."
    )
    repo_level = True

    def __init__(self, kernels_dir: Optional[str] = None,
                 ref_file: Optional[str] = None,
                 test_file: Optional[str] = None):
        self.kernels_dir = kernels_dir
        self.ref_file = ref_file
        self.test_file = test_file

    def check_repo(self, root: str) -> Iterator[Violation]:
        kdir = self.kernels_dir or os.path.join(root, "src", "repro", "kernels")
        ref_file = self.ref_file or os.path.join(kdir, "ref.py")
        test_file = self.test_file or os.path.join(
            root, "tests", "test_kernels.py")
        init_path = os.path.join(kdir, "__init__.py")
        if not os.path.isfile(init_path):
            return
        with open(init_path, encoding="utf-8") as f:
            src = f.read()
        tree = ast.parse(src, filename=init_path)

        registry_node, entries = self._parse_registry(tree)
        if registry_node is None:
            yield Violation(self.rule_id, init_path, 1, 0, 1, 0,
                            "kernels/__init__.py defines no KERNEL_REGISTRY "
                            "dict (kernel<->reference pairing)")
            return

        support = {"__init__", "ops", "ref"}
        modules = sorted(
            f[:-3] for f in os.listdir(kdir)
            if f.endswith(".py") and f[:-3] not in support
        )
        for mod in modules:
            if mod not in entries:
                yield violation_at(
                    self.rule_id, init_path, registry_node,
                    f"kernel module '{mod}.py' is not registered in "
                    f"KERNEL_REGISTRY (no paired reference)")

        ref_defs = self._top_defs(ref_file)
        test_src = self._read(test_file)
        for name, (key_node, ref_name) in entries.items():
            if ref_name is None:
                yield violation_at(
                    self.rule_id, init_path, key_node,
                    f"registry entry '{name}' has no statically readable "
                    f"ref (use a string literal)")
            elif ref_defs is not None and ref_name not in ref_defs:
                yield violation_at(
                    self.rule_id, init_path, key_node,
                    f"registry entry '{name}' names reference "
                    f"'{ref_name}' which is not defined in kernels/ref.py")
        if test_src is not None and "KERNEL_REGISTRY" not in test_src:
            yield Violation(
                self.rule_id, test_file, 1, 0, 1, 0,
                "tests/test_kernels.py does not sweep KERNEL_REGISTRY "
                "(a registered kernel could ship untested)")

    @staticmethod
    def _parse_registry(tree: ast.Module):
        """-> (dict node, {key: (key node, ref name or None)})."""
        for node in ast.walk(tree):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
            for t in targets:
                if (isinstance(t, ast.Name) and t.id == "KERNEL_REGISTRY"
                        and isinstance(node.value, ast.Dict)):
                    entries = {}
                    for k, v in zip(node.value.keys, node.value.values,
                                    strict=True):
                        if not (isinstance(k, ast.Constant)
                                and isinstance(k.value, str)):
                            continue
                        entries[k.value] = (k, Sal001KernelRegistry._ref_of(v))
                    return node.value, entries
        return None, {}

    @staticmethod
    def _ref_of(value: ast.AST) -> Optional[str]:
        """ref name out of ``KernelSpec("mod", "op", "ref")`` (positional or
        keyword) or a plain ("mod", "op", "ref") tuple."""
        args: List[ast.expr] = []
        if isinstance(value, ast.Call):
            for kw in value.keywords:
                if kw.arg == "ref" and isinstance(kw.value, ast.Constant):
                    return kw.value.value
            args = value.args
        elif isinstance(value, (ast.Tuple, ast.List)):
            args = value.elts
        if len(args) >= 3 and isinstance(args[2], ast.Constant) \
                and isinstance(args[2].value, str):
            return args[2].value
        return None

    @staticmethod
    def _top_defs(path: str) -> Optional[Set[str]]:
        src = Sal001KernelRegistry._read(path)
        if src is None:
            return None
        return {
            n.name for n in ast.parse(src).body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }

    @staticmethod
    def _read(path: str) -> Optional[str]:
        try:
            with open(path, encoding="utf-8") as f:
                return f.read()
        except OSError:
            return None


# ---------------------------------------------------------------------------
# SAL002 — no backend data reads outside the store layer
# ---------------------------------------------------------------------------


class Sal002BackendReads(Rule):
    rule_id = "SAL002"
    summary = ("StoreBackend data methods (read_items/read_chunk/"
               "read_window/gather) may only be called inside the store "
               "layer — accounting cannot be bypassed")
    rationale = (
        "The paper's residency claim is enforced by CorpusStore's byte and "
        "round accounting; a direct backend.read_items/gather call anywhere "
        "else fetches corpus bytes invisibly and silently breaks "
        "Footprint.peak_resident_bytes.  Route reads through "
        "CorpusStore.stage_items / fetch_windows / fetch_keys, or the "
        "store-layer helpers (stream_backend_items, chunk_store.load_corpus)."
    )

    METHODS: ClassVar[Set[str]] = {
        "read_items", "read_chunk", "read_window", "gather"}
    ALLOWED = ("core/store.py", "data/chunk_store.py", "core/sanitize.py")

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        if ctx.endswith(*self.ALLOWED):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute) or f.attr not in self.METHODS:
                continue
            recv = f.value
            if isinstance(recv, ast.Name) and recv.id in ("self", "cls"):
                continue  # a store-layer class calling itself
            yield violation_at(
                self.rule_id, ctx.path, node,
                f"direct backend '.{f.attr}()' call outside core/store.py "
                f"bypasses store accounting")


# ---------------------------------------------------------------------------
# SAL003 — no corpus-sized host materialization in out-of-core merge code
# ---------------------------------------------------------------------------


class Sal003MergeMaterialization(Rule):
    rule_id = "SAL003"
    summary = ("no host materialization (.tolist()/jax.device_get/np.array/"
               "dtype-converting np.asarray) inside superblock merge/tile "
               "functions unless the function registers frontier bytes")
    rationale = (
        "The out-of-core merge's whole point is that no function holds more "
        "than a tile/frontier of corpus-derived data; .tolist(), "
        "jax.device_get, np.array copies, or dtype-converting np.asarray "
        "calls inside the merge/tile functions create untracked host copies. "
        "Buffers a merge function must hold are registered via "
        "CorpusStore.add_frontier (directly or through WindowCursor._account) "
        "— a function that does so is exempt, because its residency is "
        "visible to Footprint.peak_resident_bytes."
    )

    TARGET_BASENAME = "superblock.py"
    OOC_FUNCS: ClassVar[Set[str]] = {
        "_merge_path_runs", "_kway_merge", "_merge_runs", "_refine_sort",
        "_partition", "_partition_runs", "_sorted_runs", "_rank_in_run",
        "_less_than", "_split_boundary_risk",
    }
    REGISTERED: ClassVar[Set[str]] = {"add_frontier", "_account"}

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        if ctx.basename != self.TARGET_BASENAME:
            return
        for name, fn in _func_bodies(ctx.tree).items():
            if name not in self.OOC_FUNCS:
                continue
            if _calls_name(fn, self.REGISTERED):
                continue  # residency registered with the store: tracked
            yield from self._scan(fn, ctx)

    def _scan(self, fn: ast.AST, ctx: FileContext) -> Iterator[Violation]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "tolist":
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    "'.tolist()' materializes a host list inside an "
                    "out-of-core merge function")
            elif name in ("jax.device_get", "jnp.device_get"):
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    "'jax.device_get' pulls a device buffer to host inside "
                    "an out-of-core merge function")
            elif name == "np.array" and not (
                    node.args
                    and isinstance(node.args[0], (ast.List, ast.Tuple))):
                # literal-list np.array([...]) is constant-sized, not
                # corpus-scale; anything else copies its argument.
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    "'np.array' always copies; use a view (np.asarray) or "
                    "register the buffer via add_frontier")
            elif name == "np.asarray" and (
                    len(node.args) >= 2
                    or any(kw.arg == "dtype" for kw in node.keywords)):
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    "dtype-converting 'np.asarray' copies corpus-scale data "
                    "inside an out-of-core merge function")


# ---------------------------------------------------------------------------
# SAL004 — frozen configs stay frozen
# ---------------------------------------------------------------------------


class Sal004FrozenConfigMutation(Rule):
    rule_id = "SAL004"
    summary = ("no object.__setattr__ mutation of frozen configs outside "
               "__post_init__")
    rationale = (
        "SAConfig/SuperblockConfig are frozen dataclasses so a build's "
        "geometry cannot drift mid-run (splitter math, packing and stride "
        "derivations are all cached from them).  object.__setattr__ is the "
        "one hole in the freeze and is legitimate only inside "
        "__post_init__; anywhere else it silently invalidates derived state. "
        "Use dataclasses.replace / repro.config.replace instead."
    )

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        scopes = _enclosing_scopes(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) != "object.__setattr__":
                continue
            fn, _cls = scopes.get(id(node), (None, None))
            if fn == "__post_init__":
                continue
            yield violation_at(
                self.rule_id, ctx.path, node,
                "object.__setattr__ outside __post_init__ mutates a frozen "
                "config; use dataclasses.replace")


# ---------------------------------------------------------------------------
# SAL005 — file/memmap handles must have an owner
# ---------------------------------------------------------------------------


class Sal005UnownedHandles(Rule):
    rule_id = "SAL005"
    summary = ("every open()/np.memmap in build/serve paths is owned by "
               "_Scratch, _OutputSink, core/index_io.py, "
               "data/chunk_store.py, core/journal.py, or a context manager")
    rationale = (
        "Build and serve paths run for hours and reopen indexes repeatedly; "
        "an unowned file handle or memmap leaks fds and — on the write side "
        "— risks renaming before flush.  Handles must be opened in a `with` "
        "block or belong to the audited owners: _Scratch/_OutputSink "
        "(superblock spill lifecycle) and the core/index_io.py / "
        "data/chunk_store.py modules (tmp+rename discipline)."
    )

    ALLOWED_FILES = ("core/index_io.py", "data/chunk_store.py",
                     "core/journal.py")
    OWNER_CLASSES: ClassVar[Set[str]] = {"_Scratch", "_OutputSink"}
    CALLS: ClassVar[Set[str]] = {
        "open", "np.memmap", "numpy.memmap",
        "np.lib.format.open_memmap", "numpy.lib.format.open_memmap"}

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        if ctx.endswith(*self.ALLOWED_FILES):
            return
        managed = _with_item_nodes(ctx.tree)
        scopes = _enclosing_scopes(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or id(node) in managed:
                continue
            name = dotted_name(node.func)
            mmap_load = name in ("np.load", "numpy.load") and any(
                kw.arg == "mmap_mode" for kw in node.keywords)
            if name not in self.CALLS and not mmap_load:
                continue
            _fn, cls = scopes.get(id(node), (None, None))
            if cls in self.OWNER_CLASSES:
                continue
            what = "np.load(mmap_mode=...)" if mmap_load else f"'{name}'"
            yield violation_at(
                self.rule_id, ctx.path, node,
                f"{what} outside a context manager or an audited owner "
                f"(_Scratch/_OutputSink/core/index_io.py) leaks the handle")


# ---------------------------------------------------------------------------
# SAL006 — only the installed jax API: no shims for older versions
# ---------------------------------------------------------------------------


class Sal006LegacyJaxApi(Rule):
    rule_id = "SAL006"
    summary = ("no API of a jax older than the pinned series (jax.experimental"
               ".shard_map, check_rep=, lax.pvary) and no hasattr/getattr "
               "probing of jax: call the installed API directly")
    rationale = (
        "pyproject.toml pins the jax/jaxlib series the repo runs on, the same "
        "on CPU and on the chip.  A shim that probes for an older release "
        "(hasattr(jax, ...), the jax.experimental.shard_map import, the "
        "check_rep spelling, the deprecated lax.pvary) is code no run ever "
        "takes, and it hides which API the program really calls.  Use "
        "jax.shard_map(check_vma=...), lax.axis_size and "
        "lax.pcast(..., to='varying') directly."
    )

    LEGACY_CALLS: ClassVar[Dict[str, str]] = {
        "jax.experimental.shard_map.shard_map": "jax.shard_map",
        "lax.pvary": "lax.pcast(x, axes, to='varying')",
        "jax.lax.pvary": "lax.pcast(x, axes, to='varying')",
    }

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module and (
                    node.module.startswith("jax.experimental.shard_map")):
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    "jax.experimental.shard_map is the pre-0.5 location: "
                    "use jax.shard_map")
                continue
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in self.LEGACY_CALLS:
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    f"'{name}' is a legacy jax API: use "
                    f"{self.LEGACY_CALLS[name]}")
            elif any(kw.arg == "check_rep" for kw in node.keywords):
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    "check_rep= is the pre-0.5 shard_map spelling: use "
                    "check_vma=")
            elif (name in ("hasattr", "getattr") and node.args
                  and dotted_name(node.args[0]) in ("jax", "lax", "jax.lax")):
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    f"{name}() probing of the jax API is a version shim: "
                    f"call the pinned API directly")


# ---------------------------------------------------------------------------
# SAL007 — no new internal callers of deprecated raw-array search wrappers
# ---------------------------------------------------------------------------


class Sal007DeprecatedWrapperCallers(Rule):
    rule_id = "SAL007"
    summary = ("internal code must not call the deprecated raw-array search "
               "wrappers (search_text/count_occurrences/find_occurrences/"
               "align_reads)")
    rationale = (
        "The raw-array wrappers rebuild a transient store per call — "
        "accounting-invisible and O(corpus) per query.  They exist only for "
        "external callers of the pre-store API and emit DeprecationWarning; "
        "internal code (src/, examples/) must use the "
        "store-served search_store/count_store/locate_store or "
        "SuffixArrayIndex.  Their own tests keep exercising them until "
        "removal."
    )

    DEPRECATED: ClassVar[Set[str]] = {
        "search_text", "count_occurrences", "find_occurrences", "align_reads"}

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        if ctx.basename == "search.py" or ctx.in_dir("tests"):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            called = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else None)
            if called in self.DEPRECATED:
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    f"'{called}' is a deprecated raw-array wrapper; use the "
                    f"store-served API (search_store/count_store/"
                    f"locate_store) or SuffixArrayIndex")


# ---------------------------------------------------------------------------
# SAL008 — background work goes through core/pipeline_exec only
# ---------------------------------------------------------------------------


class Sal008ThreadsOutsideExecutor(Rule):
    rule_id = "SAL008"
    summary = ("threading / concurrent.futures usage outside "
               "core/pipeline_exec.py (background work must go through "
               "PipelineExecutor)")
    rationale = (
        "The pipelined build's invariants — deterministic join on every "
        "exit path, original-exception propagation, FIFO write ordering, "
        "and prefetch bytes accounted against cache_budget_bytes — are "
        "properties of repro.core.pipeline_exec.PipelineExecutor, not of "
        "threads in general.  A raw threading.Thread or ThreadPoolExecutor "
        "elsewhere can outlive the build, swallow exceptions, reorder "
        "writes, or hold unaccounted buffers resident.  Spawn background "
        "work by submitting to a PipelineExecutor instead."
    )

    ALLOWED_FILES = ("core/pipeline_exec.py",)
    MODULES: ClassVar[Set[str]] = {"threading", "concurrent", "concurrent.futures"}
    CALLS: ClassVar[Set[str]] = {
        "threading.Thread", "Thread",
        "ThreadPoolExecutor", "ProcessPoolExecutor",
        "concurrent.futures.ThreadPoolExecutor",
        "concurrent.futures.ProcessPoolExecutor",
        "futures.ThreadPoolExecutor", "futures.ProcessPoolExecutor",
    }

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        if ctx.endswith(*self.ALLOWED_FILES):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    root = alias.name.split(".")[0]
                    if alias.name in self.MODULES or root in ("threading",
                                                              "concurrent"):
                        yield violation_at(
                            self.rule_id, ctx.path, node,
                            f"import of '{alias.name}': background work goes "
                            f"through repro.core.pipeline_exec."
                            f"PipelineExecutor, not raw threads")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "threading" or mod.startswith("concurrent"):
                    yield violation_at(
                        self.rule_id, ctx.path, node,
                        f"import from '{mod}': background work goes through "
                        f"repro.core.pipeline_exec.PipelineExecutor, not "
                        f"raw threads")
            elif isinstance(node, ast.Call):
                name = dotted_name(node.func)
                if name in self.CALLS:
                    yield violation_at(
                        self.rule_id, ctx.path, node,
                        f"'{name}' spawns unmanaged background work: submit "
                        f"to a repro.core.pipeline_exec.PipelineExecutor "
                        f"instead")


# ---------------------------------------------------------------------------
# SAL009 — no unsynchronized state shared across thread contexts (project)
# ---------------------------------------------------------------------------


class Sal009CrossContextState(Rule):
    rule_id = "SAL009"
    summary = ("state written in worker context (code reachable from "
               "PipelineExecutor.submit targets) and read in main context "
               "must be lock-guarded on both sides or handed off through "
               "the executor")
    rationale = (
        "The pipelined build's bit-identical claim rests on a strict "
        "hand-off discipline: the worker thread communicates with the main "
        "thread only through PipelineTask results and preallocated buffers "
        "it was handed.  An instance attribute or module global written by "
        "worker-context code and read by main-context code outside that "
        "discipline is a data race — exactly the class of bug the "
        "interprocedural pass exists to catch before the sharded store "
        "multiplies the surface.  Guard both sides with one lock, or route "
        "the value through the executor (submit returns a PipelineTask; its "
        "result() is the synchronized channel).  The store layer itself "
        "(core/store.py, data/chunk_store.py, core/sanitize.py) is exempt: "
        "its backend cache mutations are the audited subject of the "
        "schedule-exploration harness, which checks them dynamically."
    )
    project_level = True

    ALLOWED_FILES = ("core/store.py", "data/chunk_store.py",
                     "core/sanitize.py")

    def check_project(self, graph) -> Iterator[Violation]:
        # (class, attr) -> [(reader fn, access)] over main-context methods,
        # plus private attrs read through any receiver (``task._exc`` from
        # drain() is the same shared state as ``self._exc`` from result())
        attr_readers: Dict[Tuple[Optional[str], str], List] = {}
        private_readers: Dict[str, List] = {}
        name_readers: Dict[str, List] = {}
        for fi in graph.main:
            for acc in fi.self_reads:
                attr_readers.setdefault((fi.cls, acc.attr), []).append(
                    (fi, acc))
            for recv, acc in fi.attr_reads:
                if recv not in ("self", "cls") and acc.attr.startswith("_"):
                    private_readers.setdefault(acc.attr, []).append(
                        (fi, acc))
            for name in fi.name_reads:
                name_readers.setdefault(name, []).append(fi)
        for fi in sorted(graph.worker, key=lambda f: (f.path, f.lineno)):
            if _endswith(fi.path, self.ALLOWED_FILES):
                continue
            for acc in fi.self_writes:
                reads = list(attr_readers.get((fi.cls, acc.attr), ()))
                if acc.attr.startswith("_"):
                    reads += private_readers.get(acc.attr, ())
                reads = [(o, a) for o, a in reads if o is not fi]
                if not reads:
                    continue
                if acc.locked and all(a.locked for _o, a in reads):
                    continue
                reader, racc = min(
                    reads, key=lambda oa: (oa[0].path, oa[1].node.lineno))
                where = f"{reader.path}:{racc.node.lineno}"
                yield violation_at(
                    self.rule_id, fi.path, acc.node,
                    f"'{fi.qualname}' runs in worker context and writes "
                    f"'self.{acc.attr}', which main-context code reads at "
                    f"{where} without a lock on both sides; hand the value "
                    f"off through the executor (PipelineTask.result) or "
                    f"guard both sides with one lock")
            for acc in fi.global_writes:
                readers = [o for o in name_readers.get(acc.attr, ())
                           if o is not fi and o.path == fi.path]
                if not readers or acc.locked:
                    continue
                reader = min(readers, key=lambda o: (o.path, o.lineno))
                yield violation_at(
                    self.rule_id, fi.path, acc.node,
                    f"'{fi.qualname}' runs in worker context and writes "
                    f"global '{acc.attr}', which main-context code "
                    f"('{reader.qualname}') reads; globals cross the thread "
                    f"hand-off unsynchronized — use the executor hand-off "
                    f"or a lock")


def _endswith(path: str, suffixes: Tuple[str, ...]) -> bool:
    posix = path.replace(os.sep, "/")
    return any(posix.endswith(s) for s in suffixes)


# ---------------------------------------------------------------------------
# SAL010 — no device work or gated accounting in worker context (project)
# ---------------------------------------------------------------------------


class Sal010WorkerDeviceAccounting(Rule):
    rule_id = "SAL010"
    summary = ("worker-context code must not issue jax/device calls or "
               "mutate gated traffic counters (FetchStats accounting stays "
               "on the main thread)")
    rationale = (
        "tests/test_pipeline_exec.py gates the pipelined build on "
        "*traffic equality*: the overlapped schedule must issue exactly the requests "
        "and bytes of the synchronous one, with accounting mutated only "
        "between pipeline points on the main thread.  A worker-context "
        "jnp/jax/kops call races the main thread's device stream (and can "
        "deadlock single-device platforms); a worker-context write to a "
        "gated counter (requests, request_bytes, response_bytes, rounds, "
        "retries, peak_windows, staged_items, staged_bytes, frontier_bytes, "
        "peak_resident_bytes) or call to an accounting entry point "
        "(note_staged/note_fetched/add_frontier/stage_items/fetch_keys/"
        "fetch_windows/mget_window_host) makes the counters "
        "schedule-dependent.  Split the work: the worker runs the pure "
        "fetch half (stage_read/gather_keys), the main thread accounts at "
        "the collection point (note_staged/note_fetched)."
    )
    project_level = True

    DEVICE_PREFIXES: ClassVar[Tuple[str, ...]] = (
        "jax.", "jnp.", "lax.", "kops.")
    DEVICE_BARE: ClassVar[Set[str]] = {
        "block_until_ready", "device_put", "device_get"}
    GATED: ClassVar[Set[str]] = {
        "requests", "request_bytes", "response_bytes", "rounds", "retries",
        "peak_windows", "staged_items", "staged_bytes", "frontier_bytes",
        "peak_resident_bytes"}
    ACCOUNTING: ClassVar[Set[str]] = {
        "note_staged", "note_fetched", "add_frontier", "_note_resident",
        "stage_items", "fetch_windows", "fetch_keys", "mget_window_host"}

    def check_project(self, graph) -> Iterator[Violation]:
        for fi in sorted(graph.worker, key=lambda f: (f.path, f.lineno)):
            for dn, node in fi.dotted_calls:
                last = dn.split(".")[-1]
                if dn.startswith(self.DEVICE_PREFIXES) \
                        or last in self.DEVICE_BARE:
                    yield violation_at(
                        self.rule_id, fi.path, node,
                        f"'{fi.qualname}' runs in worker context but calls "
                        f"'{dn}': device work must stay on the main thread "
                        f"(the worker runs the pure host fetch half)")
                elif last in self.ACCOUNTING:
                    yield violation_at(
                        self.rule_id, fi.path, node,
                        f"'{fi.qualname}' runs in worker context but calls "
                        f"accounting entry point '{dn}': traffic counters "
                        f"must be mutated on the main thread at the "
                        f"collection point (note_staged/note_fetched)")
            for acc in fi.self_writes:
                if acc.attr in self.GATED:
                    yield violation_at(
                        self.rule_id, fi.path, acc.node,
                        f"'{fi.qualname}' runs in worker context but "
                        f"mutates gated counter 'self.{acc.attr}': the "
                        f"traffic-equality gate assumes main-thread "
                        f"accounting")
            for recv, acc in fi.attr_writes:
                if acc.attr in self.GATED:
                    yield violation_at(
                        self.rule_id, fi.path, acc.node,
                        f"'{fi.qualname}' runs in worker context but "
                        f"mutates gated counter '{recv}.{acc.attr}': the "
                        f"traffic-equality gate assumes main-thread "
                        f"accounting")


# ---------------------------------------------------------------------------
# SAL011 — kernel registry contract: signatures, tuning constants, dtypes
# ---------------------------------------------------------------------------


class Sal011KernelContract(Rule):
    rule_id = "SAL011"
    summary = ("every KERNEL_REGISTRY entry has kernel/op/ref signature "
               "parity (tuning params aside), matching int tile/block "
               "defaults, and int32-cast arguments at call sites")
    rationale = (
        "SAL001 checks that every kernel *has* a reference; SAL011 checks "
        "that the pair still agrees: the ops wrapper, the Pallas kernel "
        "entry point, and the ref must take the same parameters in the "
        "same order (tuning knobs block/tile/interpret aside), the tuning "
        "defaults declared by the wrapper must equal the kernel module's "
        "(a silent block-size fork makes the sweep test a lie), and "
        "explicit dtype casts at kops call sites must be int32 — the "
        "packed-key pipeline is int32 lanes end to end, and an int64 cast "
        "silently doubles device traffic.  Catching this statically turns "
        "kernel<->ref drift from a sweep-test failure into a lint line."
    )
    project_level = True

    TUNING: ClassVar[Set[str]] = {"block", "tile", "interpret"}

    def __init__(self, kernels_pkg: str = "kernels"):
        # path fragment locating the kernel package inside the scanned set;
        # fixture trees override it (e.g. "sal011_bad/kernels")
        self.kernels_pkg = kernels_pkg.rstrip("/")

    def check_project(self, graph) -> Iterator[Violation]:
        init = self._ctx(graph, "__init__.py")
        if init is None:
            return
        entries = self._parse_registry(init)
        if not entries:
            return
        ops_ctx = self._ctx(graph, "ops.py")
        ref_ctx = self._ctx(graph, "ref.py")
        ops_defs = _top_level_defs(ops_ctx)
        ref_defs = _top_level_defs(ref_ctx)
        op_names = {triple[1] for _node, triple in entries.values()}

        for key, (key_node, (module, op, ref)) in sorted(
                (k, (v[0], v[1])) for k, v in entries.items()):
            op_def = ops_defs.get(op)
            ref_def = ref_defs.get(ref)
            mod_ctx = self._ctx(graph, f"{module}.py")
            mod_def = _top_level_defs(mod_ctx).get(op)
            if ops_ctx is not None and op_def is None:
                yield violation_at(
                    self.rule_id, init.path, key_node,
                    f"registry entry '{key}' names op '{op}' which is not "
                    f"defined in {self.kernels_pkg}/ops.py")
            if ref_ctx is not None and ref_def is None:
                yield violation_at(
                    self.rule_id, init.path, key_node,
                    f"registry entry '{key}' names ref '{ref}' which is "
                    f"not defined in {self.kernels_pkg}/ref.py")
            if mod_ctx is not None and mod_def is None:
                yield violation_at(
                    self.rule_id, mod_ctx.path, mod_ctx.tree,
                    f"kernel module '{module}.py' defines no entry point "
                    f"'{op}' (the registry pairs module and op by name)")
            if op_def is not None and ref_def is not None:
                a, b = self._sig(op_def), self._sig(ref_def)
                if a != b:
                    yield violation_at(
                        self.rule_id, ref_ctx.path, ref_def,
                        f"'{ref}{tuple(b)}' does not match op "
                        f"'{op}{tuple(a)}' (tuning params aside): the "
                        f"sweep cannot call them interchangeably")
            if op_def is not None and mod_def is not None:
                a, b = self._sig(op_def), self._sig(mod_def)
                if a != b:
                    yield violation_at(
                        self.rule_id, mod_ctx.path, mod_def,
                        f"kernel entry '{op}{tuple(b)}' does not match its "
                        f"ops wrapper '{op}{tuple(a)}' (tuning params "
                        f"aside)")
                for name, default in self._tuning(op_def).items():
                    kd = self._tuning(mod_def).get(name)
                    if kd is not None and kd != default:
                        yield violation_at(
                            self.rule_id, ops_ctx.path, op_def,
                            f"op '{op}' declares {name}={default} but "
                            f"kernel module '{module}.py' declares "
                            f"{name}={kd}: tuning defaults forked")

        yield from self._check_call_sites(graph, op_names)

    # -- helpers ---------------------------------------------------------

    def _ctx(self, graph, basename: str):
        tail = f"{self.kernels_pkg}/{basename}"
        for ctx in graph.contexts:
            if ctx.posix_path.endswith(tail):
                return ctx
        return None

    def _parse_registry(self, ctx):
        """{key: (key node, (module, op, ref))} from KERNEL_REGISTRY."""
        out = {}
        for node in ast.walk(ctx.tree):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
            for t in targets:
                if not (isinstance(t, ast.Name) and t.id == "KERNEL_REGISTRY"
                        and isinstance(node.value, ast.Dict)):
                    continue
                for k, v in zip(node.value.keys, node.value.values,
                                strict=True):
                    if not (isinstance(k, ast.Constant)
                            and isinstance(k.value, str)):
                        continue
                    triple = self._triple(v)
                    if triple is not None:
                        out[k.value] = (k, triple)
        return out

    @staticmethod
    def _triple(value: ast.AST) -> Optional[Tuple[str, str, str]]:
        """(module, op, ref) from KernelSpec(...)/tuple, None if dynamic."""
        args: List[ast.expr] = []
        kw: Dict[str, str] = {}
        if isinstance(value, ast.Call):
            args = value.args
            for k in value.keywords:
                if isinstance(k.value, ast.Constant) and k.arg:
                    kw[k.arg] = k.value.value
        elif isinstance(value, (ast.Tuple, ast.List)):
            args = value.elts
        pos = [a.value for a in args
               if isinstance(a, ast.Constant) and isinstance(a.value, str)]
        fields = ("module", "op", "ref")
        got = {f: kw.get(f) for f in fields}
        for f, v in zip(fields, pos):
            if got[f] is None:
                got[f] = v
        if all(got[f] is not None for f in fields):
            return got["module"], got["op"], got["ref"]
        return None

    def _sig(self, fn: ast.AST) -> List[str]:
        args = fn.args
        names = [a.arg for a in args.posonlyargs + args.args
                 + args.kwonlyargs]
        return [n for n in names if n not in self.TUNING]

    def _tuning(self, fn: ast.AST) -> Dict[str, int]:
        """tuning param -> int literal default (non-int defaults skipped)."""
        args = fn.args
        named = args.posonlyargs + args.args
        out: Dict[str, int] = {}
        for a, d in zip(reversed(named), reversed(args.defaults)):
            if a.arg in self.TUNING and isinstance(d, ast.Constant) \
                    and type(d.value) is int:
                out[a.arg] = d.value
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is not None and a.arg in self.TUNING \
                    and isinstance(d, ast.Constant) and type(d.value) is int:
                out[a.arg] = d.value
        return out

    def _check_call_sites(self, graph, op_names: Set[str]):
        for ctx in graph.contexts:
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if not (isinstance(f, ast.Attribute)
                        and isinstance(f.value, ast.Name)
                        and f.value.id in ("kops", "ops")
                        and f.attr in op_names):
                    continue
                for arg in node.args:
                    bad = self._bad_cast(arg)
                    if bad is not None:
                        yield violation_at(
                            self.rule_id, ctx.path, arg,
                            f"argument to '{f.value.id}.{f.attr}' is cast "
                            f"to '{bad}': the packed-key pipeline is int32 "
                            f"lanes end to end")

    @staticmethod
    def _bad_cast(arg: ast.AST) -> Optional[str]:
        """dtype name when ``arg`` is an explicit non-int32 cast."""
        dtype_node = None
        if isinstance(arg, ast.Call):
            name = dotted_name(arg.func) or ""
            last = name.split(".")[-1]
            if last == "astype" and arg.args:
                dtype_node = arg.args[0]
            elif last in ("asarray", "array", "full", "zeros", "ones"):
                if len(arg.args) >= 2:
                    dtype_node = arg.args[1]
                for kwd in arg.keywords:
                    if kwd.arg == "dtype":
                        dtype_node = kwd.value
        if dtype_node is None:
            return None
        dname = dotted_name(dtype_node) or (
            dtype_node.value if isinstance(dtype_node, ast.Constant) else "")
        if isinstance(dname, str) and dname \
                and not dname.split(".")[-1].endswith("int32"):
            return dname
        return None


def _top_level_defs(ctx) -> Dict[str, ast.AST]:
    if ctx is None:
        return {}
    return {n.name: n for n in ctx.tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


# ---------------------------------------------------------------------------
# SAL012 — artifact publishes go through the sanctioned atomic helper
# ---------------------------------------------------------------------------


class Sal012AtomicPublish(Rule):
    rule_id = "SAL012"
    summary = ("artifact-publishing renames (os.replace/os.rename/"
               "shutil.move) must go through "
               "repro.core.integrity.publish_file/publish_dir")
    rationale = (
        "tmp + rename alone is not crash-safe: without an fsync of the tmp "
        "file before the rename and of the parent directory after it, a "
        "power loss can publish an empty or vanished artifact that a later "
        "open trusts.  repro.core.integrity.publish_file/publish_dir own "
        "the full durable sequence (fsync tmp -> rename -> fsync parent "
        "dir); a raw rename elsewhere silently reintroduces the torn-"
        "publish window the crash-safety tests close.  Tests simulating "
        "torn writes are exempt; genuinely rebuildable state (e.g. a lint "
        "cache) may suppress with a justification comment."
    )

    ALLOWED_FILES = ("core/integrity.py",)
    RENAMES: ClassVar[Set[str]] = {
        "os.replace", "os.rename", "os.renames", "shutil.move"}

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        if ctx.endswith(*self.ALLOWED_FILES) or ctx.in_dir("tests"):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in self.RENAMES:
                yield violation_at(
                    self.rule_id, ctx.path, node,
                    f"raw '{name}' publish is not crash-durable; use "
                    f"repro.core.integrity.publish_file/publish_dir "
                    f"(fsync tmp -> rename -> fsync parent dir)")


DEFAULT_RULES: Tuple[Rule, ...] = (
    Sal001KernelRegistry(),
    Sal002BackendReads(),
    Sal003MergeMaterialization(),
    Sal004FrozenConfigMutation(),
    Sal005UnownedHandles(),
    Sal006LegacyJaxApi(),
    Sal007DeprecatedWrapperCallers(),
    Sal008ThreadsOutsideExecutor(),
    Sal009CrossContextState(),
    Sal010WorkerDeviceAccounting(),
    Sal011KernelContract(),
    Sal012AtomicPublish(),
)
