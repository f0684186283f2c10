"""Census of the package's environment options against the table in
docs/OBSERVABILITY.md ("Env knob summary"): an option nobody can find
is not an option, and a row for a name nothing reads is a trap."""

import ast
import functools
import importlib
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "dlrover_tpu")
DOC = os.path.join(REPO, "docs", "OBSERVABILITY.md")
# A name followed by ``=`` is a line a process prints for its parent
# (``DLROVER_TPU_MASTER_PORT=41234``), not an environment option.
NAME = re.compile(r"DLROVER_TPU_[A-Z0-9_]+(?![A-Z0-9_=])")


def _code_strings(tree):
    """Every string constant of a module but its docstrings."""
    docstrings = set()
    for node in ast.walk(tree):
        if isinstance(
            node,
            (ast.Module, ast.ClassDef, ast.FunctionDef,
             ast.AsyncFunctionDef),
        ):
            first = node.body[0] if node.body else None
            if (
                isinstance(first, ast.Expr)
                and isinstance(first.value, ast.Constant)
                and isinstance(first.value.value, str)
            ):
                docstrings.add(id(first.value))
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and id(node) not in docstrings
        ):
            yield node.value


@functools.lru_cache(maxsize=None)
def _names_read():
    """{name: module} over the package's string literals. A literal
    ending in ``_`` is a family: its module reads ``PREFIX +
    knob.upper()`` for each knob of its ``DEFAULTS``, and ``PREFIX +
    "NAME"`` where it spells one out."""
    names = {}
    spelled = {}
    for root, _, files in os.walk(PACKAGE):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            with open(path) as f:
                tree = ast.parse(f.read())
            module = os.path.relpath(path, REPO)[:-3].replace(os.sep, ".")
            for text in _code_strings(tree):
                for name in NAME.findall(text):
                    names.setdefault(name, module)
            spelled[module] = [
                node.right.value
                for node in ast.walk(tree)
                if isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Add)
                and isinstance(node.left, ast.Name)
                and node.left.id.endswith("ENV_PREFIX")
                and isinstance(node.right, ast.Constant)
            ]
    for prefix in [n for n in names if n.endswith("_")]:
        module = names.pop(prefix)
        defaults = importlib.import_module(module).DEFAULTS
        for knob in [k.upper() for k in defaults] + spelled[module]:
            names.setdefault(prefix + knob, module)
    return names


@functools.lru_cache(maxsize=None)
def _table():
    """(names, family prefixes) of the table's first column. A cell
    holding ``<KNOB>`` or ``*`` documents a family."""
    with open(DOC) as f:
        text = f.read()
    section = text.split("## Env knob summary", 1)[1].split("\n## ", 1)[0]
    names, families = set(), set()
    for line in section.splitlines():
        if not line.startswith("| `DLROVER_TPU_"):
            continue
        for cell in re.findall(r"`([^`]+)`", line.split("|")[1]):
            assert cell.startswith("DLROVER_TPU_"), (
                f"write the name in full, not {cell!r}: {line[:60]}"
            )
            if cell.endswith(("<KNOB>", "*")):
                families.add(NAME.match(cell).group())
            else:
                names.add(cell)
    return names, families


def test_every_option_the_package_reads_is_in_the_table():
    names, families = _table()
    missing = sorted(
        f"{name} ({module})"
        for name, module in _names_read().items()
        if name not in names
        and not any(name.startswith(f) for f in families)
    )
    assert not missing, "no row in docs/OBSERVABILITY.md: " + ", ".join(
        missing
    )


def test_every_option_in_the_table_is_read():
    names, families = _table()
    read = _names_read()
    stale = sorted(names - set(read)) + sorted(
        f + "*" for f in families
        if not any(name.startswith(f) for name in read)
    )
    assert not stale, (
        "rows of docs/OBSERVABILITY.md nothing in dlrover_tpu/ reads: "
        + ", ".join(stale)
    )
