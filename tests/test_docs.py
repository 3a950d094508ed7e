"""The documents a reader starts from point at what the tree holds: every
path of the repository they name in backticks exists, and every flag they
hand to ``run_pretraining.py`` is one its parser has. ``PERF.md``,
``ROADMAP.md``, ``CHANGES.md`` and ``SURVEY.md`` are histories (they name what
a PR removed) and are not cases."""

import functools
import glob
import os
import re
import subprocess

import pytest

import run_pretraining

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = (["README.md", "ARCHITECTURE.md", "PARITY.md"]
             + sorted(os.path.relpath(p, REPO_ROOT) for p in glob.glob(
                 os.path.join(REPO_ROOT, "docs", "*.md")))
             + [".claude/skills/verify/SKILL.md"])
# a path is written from the root, from the package, from the benchmark or
# from the tools (the three places the documents say "in here" about)
BASES = ("", "bert_pytorch_tpu", "benchmarks", "tools")
PATH = re.compile(r"^(?:\./)?((?:[\w.-]+/)*[\w.-]+\.(?:py|sh|md|jsonl|json|csv))"
                  r"(?:::?[\w:\[\].,-]+)?$")
FENCE = re.compile(r"^```.*?^```", re.M | re.S)


def tracked_files():
    """What git would commit, and the basenames among it."""
    try:
        listed = subprocess.run(
            ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
            cwd=REPO_ROOT, capture_output=True, text=True, check=True
        ).stdout.split("\n")
        files = {f for f in listed
                 if f and os.path.exists(os.path.join(REPO_ROOT, f))}
        files = {f.replace(os.sep, "/") for f in files}
    except (OSError, subprocess.CalledProcessError):
        files = set()
    if not files:  # a checkout without git: the tree as it lies
        for folder, dirs, names in os.walk(REPO_ROOT):
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       or d == ".claude"]
            files.update(os.path.relpath(os.path.join(folder, n), REPO_ROOT)
                         .replace(os.sep, "/") for n in names)
    return files


FILES = tracked_files()
NAMES = {os.path.basename(f) for f in FILES}
# the first folder of every path as the documents write it (from a base)
FOLDERS = {f[len(base):].lstrip("/").split("/")[0]
           for base in BASES for f in FILES
           if f.startswith(base) and "/" in f[len(base):].lstrip("/")}


@functools.lru_cache(maxsize=1)
def program_sources():
    """The program's own sources (not its tests, not the benchmark) as text."""
    texts = []
    for source in sorted(FILES):
        if source.endswith(".py") and not source.startswith(
                ("tests/", "benchmarks/")):
            with open(os.path.join(REPO_ROOT, source), encoding="utf-8") as f:
                texts.append(f.read())
    return "\n".join(texts)


def own_text(text):
    """``text`` less its fenced blocks and less the first column of a table
    whose heading says that column names the REFERENCE's files (PARITY.md)."""
    lines, of_the_reference = [], False
    for line in FENCE.sub("", text).split("\n"):
        cells = line.split("|")
        if len(cells) < 3 or cells[0].strip():
            of_the_reference = False
        elif cells[1].strip().startswith("Reference"):
            of_the_reference = True
        lines.append("|".join(cells[:1] + cells[2:]) if of_the_reference
                     else line)
    return "\n".join(lines)


def named_paths(text):
    """The inline code spans of ``text`` that are one path (with, at most,
    a ``::test`` or ``:line`` behind it)."""
    spans = re.findall(r"`([^`\n]+)`", own_text(text))
    for span in spans:
        found = PATH.match(span.split(" ")[0])  # a command's file, too
        if found:
            yield found.group(1)


def missing(path):
    """Why ``path`` points nowhere, or None where it is fine or not ours."""
    if "/" not in path:
        if path in NAMES or path in program_sources():
            return None  # a file of the tree, or one that a run leaves
        return f"no file named {path} in the tree"
    if path.split("/")[0] not in FOLDERS:
        return None  # a path inside some run's output directory
    if any((base + "/" + path if base else path) in FILES for base in BASES):
        return None
    return f"{path} is not in the tree (from the root, the package, the " \
           "benchmark or the tools)"


@pytest.fixture(scope="module")
def trainer_flags():
    """Every option string of ``run_pretraining.parse_arguments``'s parser."""
    class Seen(Exception):
        pass

    def grab(parser, argv):
        raise Seen(parser)

    real = run_pretraining.parse_args_with_config_file
    run_pretraining.parse_args_with_config_file = grab
    try:
        with pytest.raises(Seen) as seen:
            run_pretraining.parse_arguments([])
    finally:
        run_pretraining.parse_args_with_config_file = real
    parser = seen.value.args[0]
    return {s for action in parser._actions for s in action.option_strings}


def trainer_commands(text):
    """The command lines of ``run_pretraining.py`` in ``text``: in a fenced
    block the line that names it with its continuation lines, in running text
    the code span that names it."""
    for block in FENCE.findall(text):
        lines = block.split("\n")
        for i, line in enumerate(lines):
            if "run_pretraining.py" not in line or line.lstrip().startswith("#"):
                continue
            command = [line.split("run_pretraining.py", 1)[1]]
            while lines[i].rstrip().endswith("\\") and i + 1 < len(lines):
                i += 1
                command.append(lines[i])
            yield " ".join(command).split(" | ")[0].split(" #")[0]
    for span in re.findall(r"`([^`\n]+)`", FENCE.sub("", text)):
        if "run_pretraining.py " in span:
            yield span.split("run_pretraining.py", 1)[1]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_files_and_flags_that_exist(document, trainer_flags):
    with open(os.path.join(REPO_ROOT, document), encoding="utf-8") as f:
        text = f.read()
    paths = sorted(set(named_paths(text)))
    assert paths, f"{document} names no file at all: the scan is broken"
    dangling = [why for why in map(missing, paths) if why]
    assert dangling == [], document
    unknown = sorted({flag for command in trainer_commands(text)
                      for flag in re.findall(r"(?<![\w-])--[a-z][\w-]*", command)
                      if flag not in trainer_flags})
    assert unknown == [], f"{document} hands run_pretraining.py {unknown}"
