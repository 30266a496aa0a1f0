"""The documents name files that exist.

README.md, eval/README.md and docs/*.md point at scripts, records and
modules by path. A path that has left the tree reads as evidence that
is not there (PR 44 found a dozen: records of time taken before the
benchmark, cited as the system's performance). One case a document: every
path under the tree's own directories that the document names, in
backticks or as a link, exists (from the root, beside the document, or
under `pio_tpu/`, as `native/eventlog.py` is written). Paths of the
reference (`/root/...`, `examples/scala-...`, `docs/manual/...`), patterns (`*`, `{a,b}`, `<name>`, `...`) and
files made at run time are not the tree's and are passed over.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREES = ("pio_tpu/", "tests/", "eval/", "docs/", "benchmark/", "examples/",
         "native/", "bin/")
ROOT_FILE = re.compile(r"^[A-Za-z_][\w.-]*\.(md|json|jsonl|py|yml)$")
DOCUMENTS = sorted(
    ["README.md", "eval/README.md"]
    + [os.path.relpath(p, REPO)
       for p in glob.glob(os.path.join(REPO, "docs", "*.md"))])
# made by a run, never committed
AT_RUN_TIME = ("eval/RMSE_PARITY_small.",)


def named_paths(text: str, doc_dir: str) -> set[str]:
    found = set()
    for token in re.findall(r"`([^`\s]+)`", text):
        token = token.split("::")[0].rstrip(".,;:")
        token = re.sub(r":\d+(-\d+)?(,\d+(-\d+)?)*$", "", token)
        if token.startswith(TREES) or (
                ROOT_FILE.match(token) and token[0].isupper()):
            found.add(token)
    for target in re.findall(r"\]\(([^)#\s]+)(?:#[^)]*)?\)", text):
        if "://" not in target:
            found.add(os.path.normpath(os.path.join(doc_dir, target)))
    return {p for p in found
            if not re.search(r"[*{}<>]|\.\.\.", p)
            and not p.startswith(
                ("examples/scala-", "docs/manual/", "..") + AT_RUN_TIME)}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    doc_dir = os.path.dirname(document)
    missing = sorted(
        p for p in named_paths(text, doc_dir)
        # `eval/RMSE_PARITY.`: a stem stands for its files
        if not any(glob.glob(os.path.join(REPO, base, p.rstrip(".") + "*"))
                   for base in ("", doc_dir, "pio_tpu")))
    assert not missing, f"{document} names {missing}"


def test_the_walk_finds_what_it_should():
    text = ("see `eval/gone.py` and `tests/test_x.py::test_y`, "
            "[perf](../PERF.md#6), `pio_tpu/ops/als.py:75-77`, "
            "`BENCH_r05.json`, `eval/*.json`, `ratings_per_s`.")
    assert named_paths(text, "docs") == {
        "eval/gone.py", "tests/test_x.py", "PERF.md",
        "pio_tpu/ops/als.py", "BENCH_r05.json"}
