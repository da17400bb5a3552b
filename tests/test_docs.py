"""The documents name only what the tree holds: every script, module,
benchmark path and top-level program a document sends its reader to exists,
and every ``jimm-tpu`` sub-command it shows is one the parser knows.

`PERF.md`, `ROADMAP.md` and `CHANGES.md` are histories and exempt: they name
what was deleted, on purpose."""

import argparse
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
DOCUMENTS = sorted(
    str(p.relative_to(REPO)) for p in (
        REPO / "README.md", *REPO.glob("docs/**/*.md"), REPO / "mkdocs.yml",
        REPO / ".github/workflows/ci.yml",
        REPO / ".claude/skills/verify/SKILL.md"))

#: directories that hold no part of the program: scratch copies, run output
NOT_THE_TREE = ("_archive_check", "_archive_parent", "_exp", "chiprun_out")

PATHED = [
    re.compile(r"\b(scripts/\w+\.py)\b"),
    re.compile(r"\b(jimm_tpu/[\w/]+\.(?:py|json))\b"),
    re.compile(r"(?<![\w/.-])(benchmarks/[\w/.-]*\w)"),
    re.compile(r"(?<![\w/.-])(tests/[\w/]+\.py)\b"),
]
MODULE_RUN = re.compile(r"python3? -m (scripts\.\w+)")
#: `name.py` with no directory before it: a top-level program, or a module
#: named by its last part
BARE = re.compile(r"(?<![\w/.*-])([A-Za-z_]\w*\.py)\b")
COMMAND = re.compile(r"(?:jimm-tpu|python3? -m jimm_tpu) ((?:[a-z][a-z-]*[ ]?)+)")


@pytest.fixture(scope="module")
def basenames():
    return {p.name for p in REPO.rglob("*.py")
            if not p.relative_to(REPO).parts[0].startswith((".", *NOT_THE_TREE))}


@pytest.fixture(scope="module")
def parser():
    from jimm_tpu.cli import build_parser
    return build_parser()


def _subcommands(parser):
    for action in parser._actions:
        if isinstance(action, argparse._SubParsersAction):
            return action.choices
    return {}


def unknown_commands(text: str, parser) -> list[str]:
    """``jimm-tpu a b ...`` runs whose words leave the parser's tree while
    the parser still asks for a sub-command."""
    wrong = []
    for run in COMMAND.findall(text):
        at, said = parser, []
        for word in run.split():
            choices = _subcommands(at)
            if not choices:
                break
            said.append(word)
            if word not in choices:
                wrong.append("jimm-tpu " + " ".join(said))
                break
            at = choices[word]
    return wrong


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_paths_a_document_names_exist(doc, basenames, parser):
    text = (REPO / doc).read_text()
    missing = {m for pattern in PATHED for m in pattern.findall(text)
               if not (REPO / m).exists()}
    missing |= {m for m in MODULE_RUN.findall(text)
                if not (REPO / (m.replace(".", "/") + ".py")).exists()}
    missing |= {m for m in BARE.findall(text) if m not in basenames}
    assert not missing, (f"{doc} names what the tree does not hold: "
                         f"{sorted(missing)}")
    assert not unknown_commands(text, parser)


def test_the_command_check_descends_into_sub_parsers(parser):
    assert unknown_commands("`jimm-tpu obs nosuchverb --adopt`", parser) == [
        "jimm-tpu obs nosuchverb"]
    assert unknown_commands("`jimm-tpu obs prof ls` and jimm-tpu train "
                            "on a chip", parser) == []
    assert unknown_commands("python -m jimm_tpu bench", parser) == [
        "jimm-tpu bench"]
