"""
Static gates as tests — the stand-in for the reference's mypy/pyflakes
pytest plugins and black-format test (reference pytest.ini and
tests/test_formatting.py). The heavy tools aren't installed in this
environment (and cannot be: no package installs), so the always-on
gates are stdlib checks: syntax, unused imports, scope-aware
undefined-name detection via ``symtable`` (the other high-signal
pyflakes check), an annotation-coverage ratchet, and tab/trailing-
whitespace hygiene. The real linters are pinned as the ``dev`` extra in
pyproject.toml and their gates run whenever they are importable, so a
normally-provisioned CI runs them for real.
"""

import ast
import builtins
import io
import os
import symtable
import tokenize

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, "gordo_tpu")


def _python_files():
    for root, _, files in os.walk(PACKAGE):
        for name in sorted(files):
            if name.endswith(".py"):
                yield os.path.join(root, name)
    for extra in ("bench.py", "__graft_entry__.py", "chip_smoke.py"):
        yield os.path.join(REPO_ROOT, extra)


FILES = sorted(_python_files())
IDS = [os.path.relpath(f, REPO_ROOT) for f in FILES]


@pytest.mark.parametrize("path", FILES, ids=IDS)
def test_syntax_and_compile(path):
    with open(path, "rb") as f:
        source = f.read()
    compile(source, path, "exec")


class _ImportUsage(ast.NodeVisitor):
    """Collect imported names (name -> lineno) and every name usage."""

    def __init__(self, noqa_lines=frozenset()):
        self.imports = {}  # name -> lineno
        self.used = set()
        self._noqa_lines = noqa_lines

    def visit_Import(self, node):
        if node.lineno not in self._noqa_lines:
            for alias in node.names:
                name = alias.asname or alias.name.split(".")[0]
                self.imports[name] = node.lineno
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if node.lineno not in self._noqa_lines:
            for alias in node.names:
                if alias.name == "*":
                    continue
                self.imports[alias.asname or alias.name] = node.lineno
        self.generic_visit(node)

    def visit_Name(self, node):
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)
        self.generic_visit(node)


@pytest.mark.parametrize("path", FILES, ids=IDS)
def test_no_unused_imports(path):
    """pyflakes' highest-signal check, via the stdlib AST."""
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source, path)
    # `# noqa` on an import line is the escape hatch for deliberate
    # re-exports outside __init__.py files.
    noqa_lines = frozenset(
        i for i, line in enumerate(source.splitlines(), 1) if "# noqa" in line
    )
    visitor = _ImportUsage(noqa_lines)
    visitor.visit(tree)

    # __init__.py re-exports and __all__ mentions count as usage.
    exported = set()
    if os.path.basename(path) == "__init__.py":
        pytest.skip("export surfaces re-import by design")
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for target in node.targets:
                if getattr(target, "id", None) == "__all__" and isinstance(
                    node.value, (ast.List, ast.Tuple)
                ):
                    exported |= {
                        c.value
                        for c in node.value.elts
                        if isinstance(c, ast.Constant)
                    }
    # String usages inside docstrings/comments don't count, but names used
    # only in annotations do appear as Name loads via ast in py3.12.
    unused = {
        name: lineno
        for name, lineno in visitor.imports.items()
        if name not in visitor.used and name not in exported and name != "_"
    }
    assert not unused, f"unused imports in {path}: {unused}"


#: names the interpreter injects at module scope
_MODULE_DUNDERS = {
    "__file__",
    "__name__",
    "__doc__",
    "__package__",
    "__spec__",
    "__loader__",
    "__path__",
    "__builtins__",
    "__debug__",
    "__annotations__",
    "__dict__",
    "__class__",
    "__module__",
    "__qualname__",
}
_BUILTIN_NAMES = set(dir(builtins)) | _MODULE_DUNDERS


def _undefined_names(path):
    """Scope-aware undefined-name detection via the stdlib ``symtable``:
    a referenced symbol that is neither assigned/imported/parameter in
    its scope, nor a closure variable, nor defined at module scope, nor
    a builtin, is a typo waiting for a rare code path."""
    with open(path) as f:
        source = f.read()
    top = symtable.symtable(source, path, "exec")
    module_defined = {
        s.get_name()
        for s in top.get_symbols()
        if s.is_assigned() or s.is_imported() or s.is_namespace()
    }
    problems = []

    def walk(table):
        for sym in table.get_symbols():
            name = sym.get_name()
            if not sym.is_referenced():
                continue
            if (
                sym.is_assigned()
                or sym.is_imported()
                or sym.is_parameter()
                or sym.is_namespace()
            ):
                continue
            if sym.is_free():
                continue  # closure variable: defined in an enclosing scope
            if name in module_defined or name in _BUILTIN_NAMES:
                continue
            problems.append((table.get_name(), table.get_lineno(), name))
        for child in table.get_children():
            walk(child)

    walk(top)
    return problems


@pytest.mark.parametrize("path", FILES, ids=IDS)
def test_no_undefined_names(path):
    problems = _undefined_names(path)
    assert not problems, f"undefined names in {path}: {problems}"


def _public_function_annotation_coverage():
    total, annotated = 0, 0
    for path in FILES:
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name.startswith("_"):
                continue
            total += 1
            args = node.args
            params = args.posonlyargs + args.args + args.kwonlyargs
            params = [a for a in params if a.arg not in ("self", "cls")]
            # zero-parameter functions count only via a return annotation
            # (all([]) is vacuously true and would let them ratchet-dodge)
            if node.returns is not None or (
                params and all(a.annotation is not None for a in params)
            ):
                annotated += 1
    return annotated, total


def test_annotation_coverage_ratchet():
    """Typing gate without mypy in the image: public functions must keep
    at least the current level of annotation coverage (a return
    annotation, or fully annotated parameters). Raise the floor as
    coverage improves; never lower it."""
    annotated, total = _public_function_annotation_coverage()
    coverage = annotated / max(total, 1)
    floor = 0.75
    assert coverage >= floor, (
        f"public-function annotation coverage fell to {coverage:.1%} "
        f"({annotated}/{total}); the ratchet floor is {floor:.0%}"
    )


@pytest.mark.parametrize("path", FILES, ids=IDS)
def test_formatting_hygiene(path):
    """Black's non-negotiables that don't need black: no tabs in
    indentation, no trailing whitespace, newline at EOF."""
    with open(path) as f:
        lines = f.readlines()
    if not lines:
        return
    offenders = []
    for i, line in enumerate(lines, 1):
        stripped = line.rstrip("\n")
        if stripped != stripped.rstrip():
            offenders.append(f"{i}: trailing whitespace")
        indent = stripped[: len(stripped) - len(stripped.lstrip())]
        if "\t" in indent:
            offenders.append(f"{i}: tab indentation")
    if not lines[-1].endswith("\n"):
        offenders.append("missing newline at EOF")
    assert not offenders, f"{path}: {offenders}"


@pytest.mark.parametrize("path", FILES, ids=IDS)
def test_tokenizes_cleanly(path):
    with open(path, "rb") as f:
        list(tokenize.tokenize(io.BytesIO(f.read()).readline))


def test_black_formatting_if_available():
    black = pytest.importorskip("black")
    import subprocess
    import sys

    result = subprocess.run(
        [sys.executable, "-m", "black", "--check", "--quiet", str(PACKAGE)],
        capture_output=True,
        text=True,
    )
    assert result.returncode == 0, result.stderr


def test_pyflakes_if_available():
    pytest.importorskip("pyflakes")
    import subprocess
    import sys

    result = subprocess.run(
        [sys.executable, "-m", "pyflakes", str(PACKAGE)],
        capture_output=True,
        text=True,
    )
    assert result.returncode == 0, result.stdout


def test_mypy_if_available():
    pytest.importorskip("mypy")
    import subprocess
    import sys

    result = subprocess.run(
        [sys.executable, "-m", "mypy", "--ignore-missing-imports", str(PACKAGE)],
        capture_output=True,
        text=True,
    )
    assert result.returncode == 0, result.stdout
