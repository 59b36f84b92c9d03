"""Every `python <file>` and `python -m <module>` command the READMEs
print names a file or module that exists — one case a command, so a
script that goes takes its instructions with it."""
import importlib.util
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READMES = ("README.md", "benchmark/README.md")
_COMMAND = re.compile(r"\bpython3?\s+(-m\s+)?([\w./-]+)")


def _commands():
    seen = []
    for readme in READMES:
        with open(os.path.join(ROOT, readme), encoding="utf-8") as f:
            for dash_m, target in _COMMAND.findall(f.read()):
                cmd = (readme, "-m " + target if dash_m else target)
                if cmd not in seen:
                    seen.append(cmd)
    return seen


def _module_exists(name: str) -> bool:
    path = os.path.join(ROOT, *name.split("."))
    if os.path.isfile(path + ".py") \
            or os.path.isfile(os.path.join(path, "__main__.py")):
        return True
    # not of this repo (`pytest`): an installed module will do
    return "." not in name and importlib.util.find_spec(name) is not None


@pytest.mark.parametrize("readme,command", _commands(),
                         ids=lambda v: v.replace("/", "_"))
def test_readme_command_names_something_that_exists(readme, command):
    if command.startswith("-m "):
        assert _module_exists(command[3:]), (readme, command)
    else:
        assert command.endswith(".py"), (readme, command)
        assert os.path.isfile(os.path.join(ROOT, command)), \
            (readme, command)


def test_the_readmes_print_commands():
    found = _commands()
    assert {r for r, _ in found} == set(READMES)
    assert ("README.md", "benchmark/run.py") in found
