"""Files the documents name exist.

Every word of a backticked span in README.md, PERF.md and
docs/guide/*.md that names a repository path — it ends in a source or
record suffix and either contains a `/` or is a bare file name — must
resolve to a file git tracks: from the root, from the package (`cp/store.py` for
`fleetflow_tpu/cp/store.py`), from the document's own directory, or, for
a bare name, anywhere in the tree. A document that still names a deleted
file fails here; the cure is to correct the document, not to allow-list
the path."""

import fnmatch
import re
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = ["README.md", "PERF.md"] + sorted(
    str(p.relative_to(REPO)) for p in (REPO / "docs/guide").glob("*.md"))
SUFFIXES = (".py", ".json", ".md", ".kdl", ".yml")
SPAN = re.compile(r"`([^`\n]+)`")

# Named on purpose though absent from the tree: the directories of a
# user's project that the loader discovers (no example project has them),
# and the output file of a command shown as an example.
ALLOWED = {"services/*.kdl", "stages/*.kdl", "variables/*.kdl",
           "capture.json"}
# generated at run time, or not paths of this repository
ALLOWED_PREFIXES = ("chiprun_out/", ".jax_cache", "_export/", "/", "~",
                    "http:", "https:")


def _tracked() -> list[str]:
    out = subprocess.run(["git", "ls-files"], cwd=REPO, text=True,
                         capture_output=True)
    if out.returncode == 0 and out.stdout:
        return out.stdout.splitlines()
    # an exported checkout (git archive) has no index: what is on disk
    return [str(p.relative_to(REPO)) for p in REPO.rglob("*")
            if p.is_file() and ".git/" not in str(p)]


def _candidates(doc: str, token: str) -> list[str]:
    here = str(Path(doc).parent)
    out = [token, f"fleetflow_tpu/{token}"]
    if here != ".":
        out.append(str(Path(here) / token))
    return out


def named_paths(text: str) -> set[str]:
    """The tokens of `text` this test holds to the tree."""
    found = set()
    # a span may be a command (`python3 benchmarks/run.py`): each word
    words = [w for span in SPAN.findall(text) for w in span.split()]
    for raw in words:
        token = re.split(r"::|#", raw)[0]           # a test id, an anchor
        token = re.sub(r":[\d,:-]+$", "", token)    # file.py:12, :12-40
        token = token.rstrip(".,;)").lstrip("(")
        if not token.endswith(SUFFIXES):
            continue
        if any(c in token for c in "<>{}$= "):      # a placeholder, a command
            continue
        if token.startswith(ALLOWED_PREFIXES) or token in ALLOWED:
            continue
        found.add(token)
    return found


@pytest.fixture(scope="module")
def tracked():
    files = _tracked()
    return files, {Path(f).name for f in files}


@pytest.mark.parametrize("doc", DOCS)
def test_named_files_exist(doc, tracked):
    files, basenames = tracked
    dangling = []
    for token in sorted(named_paths((REPO / doc).read_text())):
        if "/" not in token:
            ok = (token in basenames if "*" not in token
                  else bool(fnmatch.filter(basenames, token)))
        else:
            ok = any(fnmatch.filter(files, c) if "*" in c else c in files
                     for c in _candidates(doc, token))
            # a project's own layout (`.fleetflow/fleet.kdl`): some tracked
            # project has it
            ok = ok or bool(fnmatch.filter(files, f"*/{token}"))
        if not ok:
            dangling.append(token)
    assert not dangling, f"{doc} names files that do not exist: {dangling}"
