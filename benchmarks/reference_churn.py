"""The plain reference of a churn reply that carries what moved.

`placement.node_events` asked with `"reply": "moved"` answers, for each
re-solved stage, `{row: server}` for exactly the rows whose server differs
from the placement the burst started from. A client that holds that
placement applies the map and holds the new one. This file says what such
a map has to be, in plain Python over dicts: it imports nothing of the
program and nothing the program imports.

    apply_moved(before, moved)          the client's side: the new assignment
    expected_moved(before, after)       what the reply must be, given both
    check_moved(before, moved, offline, rows)
                                        faults of a reply, given what the
                                        client can know: its own assignment,
                                        the servers it reported offline, the
                                        stage's row count

Whether the applied assignment is a feasible placement is
`benchmarks/checker.py`'s to say, over all of its rows.
"""

from __future__ import annotations

KINDS = ("unknown_row", "needless", "left_on_offline", "row_count")


def apply_moved(before: dict[str, str], moved: dict[str, str]
                ) -> dict[str, str]:
    """`before` with every row of `moved` on its new server. A row the map
    lacks has not moved."""
    after = dict(before)
    after.update(moved)
    return after


def expected_moved(before: dict[str, str], after: dict[str, str]
                   ) -> dict[str, str]:
    """The rows of `after` that `before` has on another server, or lacks."""
    return {row: server for row, server in after.items()
            if before.get(row) != server}


def check_moved(before: dict[str, str], moved: dict[str, str],
                offline, rows: int) -> dict[str, int]:
    """Count what is wrong with a reply's `moved` map, per kind; `total`
    is their sum and 0 means the reply is what it says it is.

    unknown_row      the map names a row the client does not hold
    needless         a row mapped to the server it was already on
    left_on_offline  a row that sat on an offline server and is not in the
                     map, or is mapped onto an offline server
    row_count        `rows`, the stage's count, is not the client's
    """
    dead = set(offline)
    out = dict.fromkeys(KINDS, 0)
    for row, server in moved.items():
        if row not in before:
            out["unknown_row"] += 1
        elif before[row] == server:
            out["needless"] += 1
        if server in dead:
            out["left_on_offline"] += 1
    out["left_on_offline"] += sum(
        1 for row, server in before.items()
        if server in dead and row not in moved)
    out["row_count"] = int(rows != len(before))
    out["total"] = sum(out[k] for k in KINDS)
    return out
