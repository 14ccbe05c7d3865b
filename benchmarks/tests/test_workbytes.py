"""The bytes function against a hand count at two sizes."""

import run as R
from harness.workbytes import columns_read, field_type, search_bytes

MAPPINGS = R.read_json(R.HERE, "configs", "http_logs-1shard.json")["mappings"]
SPECS = {op["name"]: op["spec"] for op in R.read_json(
    R.HERE, "traffic", "track-searches.json")["operations"]}


def test_field_types_come_from_the_mapping():
    assert field_type(MAPPINGS, "request.raw") == "keyword"
    assert field_type(MAPPINGS, "@timestamp") == "date"
    assert field_type(MAPPINGS, "size") == "integer"


def test_columns_of_each_operation():
    assert columns_read(SPECS["default"]) == []
    assert columns_read(SPECS["term"]) == ["request.raw"]
    assert columns_read(SPECS["status-200s-in-range"]) == ["@timestamp",
                                                           "status"]
    assert columns_read(SPECS["hourly_agg"]) == ["@timestamp"]
    assert columns_read(SPECS["asc_sort_size"]) == ["size"]


def test_hand_count():
    n = 524288
    assert search_bytes(MAPPINGS, SPECS["default"], n) == 0
    # a keyword's ordinal: 4 bytes a document, 2 MiB
    assert search_bytes(MAPPINGS, SPECS["term"], n) == 2_097_152
    # a date is a long: 8 bytes a document
    assert search_bytes(MAPPINGS, SPECS["range"], n) == 4_194_304
    assert search_bytes(MAPPINGS, SPECS["hourly_agg"], n) == 4_194_304
    # the date and the integer status
    assert search_bytes(MAPPINGS, SPECS["status-400s-in-range"], n) \
        == 6_291_456
    assert search_bytes(MAPPINGS, SPECS["desc_sort_size"], 131072) == 524_288
