"""The chip tools name things the package still has.

`eval/*.py` and `chip_smoke.py` run on the chip, minutes a call, and
import most of `pio_tpu` inside their functions: a name that left the
package (PR 44 took a trainer entry and a utility module away under six
of them) would be found there and not here. One case a script:
every `from pio_tpu... import name` it holds, at any depth, resolves, and
so does every `module.name` it reads off a module imported that way.
"""

import ast
import glob
import importlib
import os
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "eval", "*.py"))) + [
        "chip_smoke.py"]


def unresolved(source: str) -> list[str]:
    tree = ast.parse(source)
    modules: dict[str, types.ModuleType] = {}
    missing = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ImportFrom) and node.module
                and node.module.split(".")[0] == "pio_tpu"):
            continue
        try:
            module = importlib.import_module(node.module)
        except ImportError:
            missing.append(node.module)
            continue
        for alias in node.names:
            if hasattr(module, alias.name):
                held = getattr(module, alias.name)
            else:
                try:
                    held = importlib.import_module(
                        f"{node.module}.{alias.name}")
                except ImportError:
                    missing.append(f"{node.module}.{alias.name}")
                    continue
            if isinstance(held, types.ModuleType):
                modules[alias.asname or alias.name] = held
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in modules
                and not hasattr(modules[node.value.id], node.attr)):
            missing.append(f"{modules[node.value.id].__name__}.{node.attr}")
    return sorted(set(missing))


@pytest.mark.parametrize("script", SCRIPTS)
def test_a_chip_tool_names_what_the_package_has(script):
    with open(os.path.join(REPO, script)) as f:
        assert unresolved(f.read()) == []


def test_the_walk_finds_a_name_that_left():
    source = (
        "def main():\n"
        "    from pio_tpu.ops import als\n"
        "    from pio_tpu.ops.als import ALSParams, no_such_trainer\n"
        "    from pio_tpu.utils.no_such_module import anything\n"
        "    return als.als_train, als.no_such_function\n")
    assert unresolved(source) == [
        "pio_tpu.ops.als.no_such_function",
        "pio_tpu.ops.als.no_such_trainer",
        "pio_tpu.utils.no_such_module"]
