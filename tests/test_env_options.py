"""The package's `FLEET_*` environment options and their one table.

ROADMAP Queue 3's rule is that the count of options does not go up. The
set of names the package reads (the roadmap's own count: every
`FLEET_[A-Z_]+` in `fleetflow_tpu/**/*.py`, comments included, so a name
cannot linger in a docstring after its read is gone) must equal the set
in the table of docs/guide/07-operations.md, both ways."""

import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
NAME = re.compile(r"FLEET_[A-Z_]+")
ROW = re.compile(r"^\| `(FLEET_[A-Z_]+)\*?` \|", re.M)


def _read_by_package() -> set[str]:
    names: set[str] = set()
    for path in (REPO / "fleetflow_tpu").rglob("*.py"):
        names.update(NAME.findall(path.read_text()))
    return names


def _documented() -> set[str]:
    guide = (REPO / "docs/guide/07-operations.md").read_text()
    table = guide[guide.index("## Environment options"):]
    return set(ROW.findall(table))


def test_every_option_read_is_documented():
    missing = _read_by_package() - _documented()
    assert not missing, (
        f"read by the package, absent from the table in "
        f"docs/guide/07-operations.md: {sorted(missing)}")


def test_every_documented_option_is_read():
    stale = _documented() - _read_by_package()
    assert not stale, (
        f"in the table of docs/guide/07-operations.md, read by nothing in "
        f"fleetflow_tpu/: {sorted(stale)}")
