"""Codebase-level lint: AST-walk every module under paddle_tpu/ and ban
three defect-prone patterns — the source-level counterpart of the
metric-name lint from the observability PR (tests/test_metric_names.py):

- bare ``except:`` — swallows KeyboardInterrupt/SystemExit and hides
  real faults (the resilience layer's retry filters depend on
  exception types propagating);
- mutable default arguments — shared across calls, a classic
  state-leak between Programs/tests;
- ``lock.acquire()`` outside a ``with`` statement — a raise between
  acquire and release deadlocks the serving workers / training loop
  (every lock in the codebase is expected to use context-manager form);
- ``threading.Thread(...)`` without an explicit ``daemon=`` — a
  non-daemon worker thread keeps the interpreter alive after the main
  thread exits (hung test runs, hung serving shutdowns);
- ``dict.setdefault(k, <side-effectful call>)`` — the default is
  evaluated EVERY call, even when the key exists: an expensive or
  stateful constructor (``threading.Lock()``, optimizer-state
  materialization) runs and is thrown away, and the discarded object's
  side effects already happened.

And two checks of the flag table (paddle_tpu/flags.py) against the
code: a flag whose reader was deleted must leave the table with it, and
an environment variable the package reads must be in the table.

And the op layer's rule: a kernel file under ops/pallas/ is reached
from an op rule, an op rule chooses its path from what it is handed or
through the one test lever (ops/pallas/__init__.py pallas_dispatch) and
never from an environment read of its own, and the flash crossover has
one owner.
"""
import ast
import os
import re

import pytest

_PKG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "paddle_tpu")


def _py_files():
    for root, dirs, files in os.walk(_PKG):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(root, f)


def _rel(path):
    return os.path.relpath(path, os.path.dirname(_PKG))


def _parse(path):
    with open(path, encoding="utf-8") as f:
        return ast.parse(f.read(), filename=path)


def test_no_bare_except():
    offenders = []
    for path in _py_files():
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                offenders.append(f"{_rel(path)}:{node.lineno}")
    assert not offenders, (
        "bare `except:` swallows KeyboardInterrupt/SystemExit — catch "
        "Exception (or narrower):\n  " + "\n  ".join(offenders))


def test_no_mutable_default_args():
    offenders = []
    for path in _py_files():
        for node in ast.walk(_parse(path)):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                continue
            defaults = list(node.args.defaults) + \
                [d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                        isinstance(d, ast.Call)
                        and isinstance(d.func, ast.Name)
                        and d.func.id in ("list", "dict", "set")):
                    name = getattr(node, "name", "<lambda>")
                    offenders.append(
                        f"{_rel(path)}:{d.lineno} in {name}()")
    assert not offenders, (
        "mutable default arguments are shared across calls — default "
        "to None and construct inside the function:\n  "
        + "\n  ".join(offenders))


def test_no_lock_acquire_outside_with():
    """Any ``<expr>.acquire(...)`` call must appear as (part of) a
    ``with`` item; explicit acquire/release pairs leak the lock when
    the critical section raises."""
    offenders = []
    for path in _py_files():
        tree = _parse(path)
        with_calls = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    for sub in ast.walk(item.context_expr):
                        if isinstance(sub, ast.Call):
                            with_calls.add(id(sub))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "acquire" \
                    and id(node) not in with_calls:
                offenders.append(f"{_rel(path)}:{node.lineno}")
    assert not offenders, (
        "lock.acquire() outside a `with` statement — use the lock as a "
        "context manager so a raise cannot leak it:\n  "
        + "\n  ".join(offenders))


# names whose bare-call results are cheap and side-effect-free; calling
# them redundantly in a setdefault default is harmless by construction
_PURE_BUILTIN_CALLS = frozenset({
    "list", "dict", "set", "tuple", "frozenset", "len", "int", "float",
    "str", "bool", "bytes"})


def _thread_without_daemon(tree):
    """Yield ``threading.Thread(...)`` / ``Thread(...)`` calls that do
    not pass ``daemon=`` explicitly."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        named = (isinstance(f, ast.Attribute) and f.attr == "Thread") \
            or (isinstance(f, ast.Name) and f.id == "Thread")
        if named and not any(kw.arg == "daemon"
                             for kw in node.keywords):
            yield node


def _setdefault_with_side_effectful_default(tree):
    """Yield ``<expr>.setdefault(k, <Call>)`` where the default is a
    call NOT on the pure-builtin allowlist: the call runs on every
    lookup, even when the key already exists."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "setdefault"
                and len(node.args) >= 2):
            continue
        d = node.args[1]
        if isinstance(d, ast.Call) and not (
                isinstance(d.func, ast.Name)
                and d.func.id in _PURE_BUILTIN_CALLS):
            yield node


def test_no_thread_without_explicit_daemon():
    offenders = []
    for path in _py_files():
        for node in _thread_without_daemon(_parse(path)):
            offenders.append(f"{_rel(path)}:{node.lineno}")
    assert not offenders, (
        "threading.Thread(...) without daemon= — a non-daemon worker "
        "keeps the interpreter alive after main exits; pass "
        "daemon=True (or an explicit daemon=False with a join path):"
        "\n  " + "\n  ".join(offenders))


def test_no_setdefault_with_side_effectful_default():
    offenders = []
    for path in _py_files():
        for node in _setdefault_with_side_effectful_default(
                _parse(path)):
            offenders.append(f"{_rel(path)}:{node.lineno}")
    assert not offenders, (
        "dict.setdefault(k, <call>) evaluates the default on EVERY "
        "lookup — guard with `if k not in d:` / `d.get(k)` so the "
        "constructor only runs when the key is missing:\n  "
        + "\n  ".join(offenders))


@pytest.mark.parametrize("snippet,expected", [
    ("try:\n    pass\nexcept:\n    pass\n", "bare"),
    ("def f(x=[]):\n    return x\n", "mutable"),
    ("import threading\nl = threading.Lock()\nl.acquire()\n", "acquire"),
    ("import threading\nthreading.Thread(target=f)\n", "thread"),
    ("d = {}\nd.setdefault('k', make_state(x))\n", "setdefault"),
])
def test_lint_rules_detect_planted_defects(tmp_path, snippet, expected):
    """The rules themselves catch planted violations (guards against a
    lint that silently stopped matching anything)."""
    tree = ast.parse(snippet)
    if expected == "bare":
        assert any(isinstance(n, ast.ExceptHandler) and n.type is None
                   for n in ast.walk(tree))
    elif expected == "mutable":
        assert any(isinstance(n, ast.FunctionDef)
                   and any(isinstance(d, ast.List)
                           for d in n.args.defaults)
                   for n in ast.walk(tree))
    elif expected == "acquire":
        assert any(isinstance(n, ast.Call)
                   and isinstance(n.func, ast.Attribute)
                   and n.func.attr == "acquire"
                   for n in ast.walk(tree))
    elif expected == "thread":
        assert list(_thread_without_daemon(tree))
    else:
        assert list(_setdefault_with_side_effectful_default(tree))


@pytest.mark.parametrize("snippet", [
    # explicit daemon= (either value) satisfies the thread rule
    "import threading\nthreading.Thread(target=f, daemon=True)\n",
    "import threading\nthreading.Thread(target=f, daemon=False)\n",
    # pure-builtin and literal defaults satisfy the setdefault rule
    "d = {}\nd.setdefault('k', [])\n",
    "d = {}\nd.setdefault('k', tuple(x))\n",
    "d = {}\nd.setdefault('k', len(x))\n",
])
def test_lint_rules_allow_benign_forms(snippet):
    tree = ast.parse(snippet)
    assert not list(_thread_without_daemon(tree))
    assert not list(_setdefault_with_side_effectful_default(tree))


# ---------------------------------------------------------------------------
# the flag table against the code
# ---------------------------------------------------------------------------
_ROOT = os.path.dirname(_PKG)
_FLAG_NAME = re.compile(r"PADDLE_TPU_[A-Z0-9_]+")


def _string_constants(paths):
    """Every whole string literal of `paths` (a docstring that merely
    mentions a name is a longer string and does not count)."""
    for path in paths:
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, str):
                yield node.value, path


def _package_code():
    return [p for p in _py_files()
            if os.path.basename(p) != "flags.py"]


def test_every_registered_flag_is_read():
    """A name in flags.FLAGS is a string some code hands to an
    environment read: in the package or in the parity scripts under
    benchmarks/. A flag left in the table after its reader went fails
    here."""
    from paddle_tpu import flags
    harness = [
        os.path.join(_ROOT, "benchmarks", f)
        for f in sorted(os.listdir(os.path.join(_ROOT, "benchmarks")))
        if f.endswith(".py")]
    named = {s for s, _ in _string_constants(_package_code() + harness)}
    unread = sorted(n for n in flags.FLAGS if n not in named)
    assert not unread, (
        "flags.FLAGS rows nothing reads (delete the row with its "
        f"reader): {unread}")


def test_every_env_read_is_registered():
    """Every PADDLE_TPU_* name the package uses as a string
    (os.environ.get and the helpers that wrap it) has its row in
    flags.FLAGS."""
    from paddle_tpu import flags
    missing = sorted({
        f"{s} ({_rel(path)})"
        for s, path in _string_constants(_package_code())
        if _FLAG_NAME.fullmatch(s) and s not in flags.FLAGS})
    assert not missing, (
        "environment variables read but not in paddle_tpu/flags.py: "
        f"{missing}")


# -- the op layer ------------------------------------------------------------

_PALLAS = os.path.join(_PKG, "ops", "pallas")
_KERNELS = sorted(f[:-3] for f in os.listdir(_PALLAS)
                  if f.endswith(".py") and f != "__init__.py")


def _imports(path):
    """(absolute module, imported name) of every `from m import n` and
    (module, None) of every `import m` in `path`, relative imports
    resolved against the file's own package."""
    pkg = os.path.relpath(os.path.dirname(path), _ROOT).split(os.sep)
    for node in ast.walk(_parse(path)):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, None
        elif isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            for a in node.names:
                yield mod, a.name


@pytest.mark.parametrize("kernel", _KERNELS)
def test_every_pallas_kernel_is_reached_from_an_op_rule(kernel):
    """A kernel file nothing in the package imports has no op rule in
    front of it: no cell, model or user program can run it, and its
    only readers are its own tests. It goes, or an op rule takes it."""
    exported = {n for m, n in _imports(os.path.join(_PALLAS, "__init__.py"))
                if m == f"paddle_tpu.ops.pallas.{kernel}"}
    users = []
    for path in _py_files():
        if os.path.dirname(path) == _PALLAS:
            continue
        for mod, name in _imports(path):
            full = mod if name is None else f"{mod}.{name}"
            if (full == f"paddle_tpu.ops.pallas.{kernel}"
                    or mod == f"paddle_tpu.ops.pallas.{kernel}"
                    or (mod == "paddle_tpu.ops.pallas"
                        and name in exported)):
                users.append(_rel(path))
    assert users, (
        f"ops/pallas/{kernel}.py is imported by no module of paddle_tpu/ "
        "outside ops/pallas/")


_OP_RULE_FILES = ["ops/nn_ops.py", "ops/sequence_ops.py", "ops/moe_ops.py",
                  "ops/cache_ops.py", "ops/control_flow_ops.py",
                  "parallel/context_parallel.py"]


@pytest.mark.parametrize("rel", _OP_RULE_FILES)
def test_op_rules_read_no_environment(rel):
    """An op rule is traced from shapes, dtypes, the backend and the
    mesh; the one environment reader the op layer has is
    ops/pallas/__init__.py pallas_dispatch, the tests' lever onto each
    kernel's path. A switch inside a rule is a path no cell measures."""
    with open(os.path.join(_PKG, rel), encoding="utf-8") as f:
        src = f.read()
    reads = re.findall(r"\bos\.(?:environ|getenv)\b|\bfrom os import\b",
                       src)
    assert not reads, f"{rel} reads the environment: {reads}"


def test_flash_crossover_has_one_owner():
    """The sequence length from which a dispatcher that was left the
    choice takes the flash kernels is ONE constant of the flash module:
    ops/nn_ops.py _sdpa and parallel/context_parallel.py both import it
    and nothing else defines it."""
    owner = "paddle_tpu.ops.pallas.flash_attention"
    taken = {}
    for rel in ("ops/nn_ops.py", "parallel/context_parallel.py"):
        taken[rel] = {n for m, n in _imports(os.path.join(_PKG, rel))
                      if m == owner and n and n.isupper()}
    names = set().union(*taken.values())
    assert len(names) == 1 and all(taken.values()), taken
    (name,) = names
    defined = []
    for path in _py_files():
        for node in ast.walk(_parse(path)):
            targets = node.targets if isinstance(node, ast.Assign) else (
                [node.target] if isinstance(node, ast.AnnAssign) else [])
            if any(isinstance(t, ast.Name) and t.id == name
                   for t in targets):
                defined.append(_rel(path))
    assert defined == ["paddle_tpu/ops/pallas/flash_attention.py"], defined
