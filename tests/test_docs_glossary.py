"""The §10b glossary is under contract.

An earlier glossary contradicted the line it glossed and quoted
measured values with no run behind them.  These tests make that class
of drift a CI failure:

1. every compact-line key (`bench.py COMPACT_PICKS`) has a glossary
   row — a new bench field cannot ship undocumented;
2. any measured value a glossary row quotes must name where it came
   from (`PERF.md` / the driver's ledger) or an external source
   ("sourced" / "reference") — no unstamped constants.  Since PR 21 no
   row quotes one: nothing has been measured on the current code.
"""

import os
import re

_DOCS = os.path.join(
    os.path.dirname(__file__), os.pardir, "docs", "architecture.md"
)
_REPO = os.path.join(os.path.dirname(__file__), os.pardir)


def _glossary_rows():
    """Table rows of §10b, header to the first non-table paragraph."""
    with open(_DOCS) as f:
        text = f.read()
    start = text.index("### 10b.")
    block = text[start:]
    rows = []
    in_table = False
    for line in block.splitlines():
        if line.startswith("|"):
            in_table = True
            rows.append(line)
        elif in_table and line.strip():
            break  # first prose line after the table ends the glossary
    assert len(rows) > 10, "glossary table not found under §10b"
    # drop the header + separator rows
    return [r for r in rows if not re.match(r"^\|\s*(key|[-| ]+)\s*\|", r)]


# a number wearing a rate unit, a measured ratio (1.41×-style), or an
# explicit "certified" claim — the signals that a row QUOTES a result
# (thresholds like ">=1.5" and config like "batch 32" don't match)
_MEASURED = re.compile(
    r"\d[\d,]*(?:\.\d+)?\s*(?:tok/s|img/s|req/s)|\d(?:\.\d+)?×|\bcertified\b"
)
_SOURCED = re.compile(r"PERF\.md|ledger|sourced|reference", re.IGNORECASE)


def test_every_compact_key_has_a_glossary_row():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(_REPO, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    table = "\n".join(_glossary_rows())
    missing = [
        key for key, _ in bench.COMPACT_PICKS if f"`{key}`" not in table
    ]
    assert not missing, f"compact-line keys with no §10b glossary row: {missing}"


def test_no_unstamped_measured_constants():
    offenders = [
        row for row in _glossary_rows()
        if _MEASURED.search(row) and not _SOURCED.search(row)
    ]
    assert not offenders, (
        "glossary rows quote measured values without naming PERF.md, the "
        f"ledger or an external source: {offenders}"
    )
