"""Bytes a search cannot avoid reading, from the mapping and the
operation's spec alone.

Exact totals mean that every document's value is looked at in each
column the query filters, sorts or buckets on; no engine's layout goes
into the count, so it reads the same whichever program runs. A column
holds one value a document: 8 bytes for a date (a long), 4 for an
integer, 4 for a keyword's ordinal. `match_all` reads no column. A
sorted index or postings would let a range or a term read less; the
count is of the columnar pass the program makes today, and a later
change that reads less shows as a higher share, up to the point where
the function has to be counted anew.

A `match` or a `phrase` on a text field reads postings and norms, not a
column: this function counts none of that, and says so. The metric that
reads it lists the cells it is read in (`column_scan_roofline`'s
`workloads` in `BENCHMARK.json`); the fused engines' own operations and
bytes are a function and a metric of their own.
"""

from __future__ import annotations

COLUMN_BYTES = {"date": 8, "integer": 4, "long": 8, "keyword": 4, "ip": 4}


def field_type(mappings: dict, field: str) -> str:
    """`request.raw` is the sub-field `raw` of `request`."""
    name, _, sub = field.partition(".")
    prop = mappings["properties"][name]
    return prop["fields"][sub]["type"] if sub else prop["type"]


def columns_read(spec: dict) -> list[str]:
    for c in spec["clauses"]:
        if "match" in c or "phrase" in c:
            raise ValueError(
                f"workbytes counts columnar passes only: a match or a phrase "
                f"on [{c['field']}] reads postings, which it does not count; "
                f"keep this cell off column_scan_roofline's workloads")
    fields = [c["field"] for c in spec["clauses"]]
    if spec.get("sort"):
        fields.append(spec["sort"]["field"])
    if spec.get("histogram"):
        fields.append(spec["histogram"]["field"])
    return sorted(set(fields))


def search_bytes(mappings: dict, spec: dict, docs: int) -> int:
    """Bytes one search of `spec` reads over `docs` documents, however
    many shards hold them."""
    return docs * sum(COLUMN_BYTES[field_type(mappings, f)]
                      for f in columns_read(spec))
