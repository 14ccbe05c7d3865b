"""`parse_date_millis` reads a fixed-layout ISO-8601 date-time by slicing
(`_iso_millis`) and sends everything else through the `_DATE_FORMATS`
loop it always had. Every value has to come out of both the same: the
same millis, or the same MapperParsingError."""

import calendar
import numbers
import random
import re

import pytest

from elasticsearch_tpu.index import mapping
from elasticsearch_tpu.index.mapping import parse_date_millis
from elasticsearch_tpu.utils.errors import MapperParsingError


def format_loop_only(value) -> int:
    """`parse_date_millis` as it was before the fast path."""
    if isinstance(value, bool):
        raise MapperParsingError(f"cannot parse boolean [{value}] as date")
    if isinstance(value, numbers.Number):
        return int(value)
    s = str(value).strip()
    if re.fullmatch(r"[+-]?\d{10,}", s):
        return int(s)
    return mapping._format_loop_millis(s, value)


def outcome(parse, value):
    try:
        return parse(value)
    except MapperParsingError as e:
        return type(e).__name__, str(e)


VALUES = [
    # the layouts the fast path takes
    "1998-05-01T10:20:30Z", "1998-05-01T10:20:30", "1998-05-01T10:20:30+02:00",
    "1998-05-01T10:20:30-07:30", "1998-05-01T10:20:30+00:00",
    "1998-05-01T10:20:30-00:00", "1998-05-01T10:20:30+23:59",
    "1998-05-01T10:20:30.1Z", "1998-05-01T10:20:30.12Z",
    "1998-05-01T10:20:30.123Z", "1998-05-01T10:20:30.1234Z",
    "1998-05-01T10:20:30.12345Z", "1998-05-01T10:20:30.123456Z",
    "1998-05-01T10:20:30.999999", "1998-05-01T10:20:30.5+05:30",
    "1969-12-31T23:59:59Z", "1969-12-31T23:59:59.999Z", "1969-07-20T20:17:40.25Z",
    "0001-01-01T00:00:00Z", "9999-12-31T23:59:59.999999Z",
    "2000-02-29T00:00:00Z", "2024-02-29T12:00:00+01:00",
    "  1998-05-01T10:20:30Z  ", "1970-01-01T00:00:00Z", "2038-01-19T03:14:08Z",
    # near misses: the loop decides, the fast path has to stand aside
    "1998-05-01T10:20:30.1234567Z", "1998-05-01T10:20:30.Z", "1998-05-01T10:20:30z",
    "1998-05-01t10:20:30Z", "1998-05-01T10:20:30+0200", "1998-05-01T10:20:30+02",
    "1998-05-01T10:20:30+02:00:30", "1998-05-01T10:20:30+24:00",
    "1998-05-01T10:20:30+12:60", "1998-05-01T10:20:30 Z", "1998-05-01T10:20:30ZZ",
    "1998-05-01T24:00:00Z", "1998-05-01T10:60:00Z", "1998-05-01T10:20:60Z",
    "1998-05-01T10:20:61Z", "1998-02-30T10:20:30Z", "1999-02-29T00:00:00Z",
    "1998-13-01T10:20:30Z", "1998-00-10T10:20:30Z", "1998-05-00T10:20:30Z",
    "0000-01-01T00:00:00Z", "1998-5-1T1:2:3Z", "98-05-01T10:20:30Z",
    "1998-05-01T10:20Z", "1998-05-01T10Z", "1998-05-01T", "1998-05-01T10:20:3Z",
    "1998-05-01T1०:20:30Z", "1998/05/01T10:20:30Z", "1998-05-01T10.20.30Z",
    "+998-05-01T10:20:30Z", "1998-05-01T-1:20:30Z", "1998-05-01T10:20:30.-1Z",
    "1998-05-01T10:20:30.1 Z", "1998-05-01T10:20:30,123Z",
    # the loop's other formats
    "1998-05-01", "1998-05-01 10:20:30", "1998-05-01 10:20:30.5",
    "01/May/1998:10:20:30 +0000", "30/Apr/1998:21:30:17 -0700",
    "1998-05-01 10:20", "19980501", "1998-05-01Z",
    # epoch strings and numbers
    "893413230000", "-893413230000", "+893413230000", "1234567890",
    "123456789", "12345678901234567890", 893413230000, 0, -1, 12.75, 1e12,
    # never a date
    True, False, "", " ", "yesterday", "the day after", "T", "null", None,
    "1998-05-01T10:20:30Z; DROP", [], {},
]


@pytest.mark.parametrize("value", VALUES, ids=repr)
def test_fast_path_and_format_loop_agree(value):
    assert outcome(parse_date_millis, value) == \
        outcome(format_loop_only, value)


def test_the_fast_path_takes_what_the_corpus_sends():
    """...and leaves alone what it was not written for."""
    instant = 1000 * calendar.timegm((1998, 5, 1, 10, 20, 30))
    assert mapping._iso_millis("1998-05-01T10:20:30Z") == instant
    assert mapping._iso_millis("1998-05-01T10:20:30.250+02:00") == \
        instant - 2 * 3600 * 1000 + 250
    for other in ("1998-05-01", "01/May/1998:10:20:30 +0000", "894018030000",
                  "1998-05-01 10:20:30", "1998-05-01T10:20:30+0200"):
        assert mapping._iso_millis(other) is None


def test_random_instants_agree():
    """The float arithmetic of `datetime.timestamp() * 1000`, fraction
    digits, offsets and years on both sides of 1970."""
    rng = random.Random(26)
    for _ in range(20000):
        s = "%04d-%02d-%02dT%02d:%02d:%02d" % (
            rng.choice((rng.randint(1, 9999), rng.randint(1960, 2040))),
            rng.randint(1, 12), rng.randint(1, 28), rng.randint(0, 23),
            rng.randint(0, 59), rng.randint(0, 59))
        digits = rng.randint(0, 6)
        if digits:
            s += "." + "".join(rng.choice("0123456789") for _ in range(digits))
        s += rng.choice(("", "Z", "%s%02d:%02d" % (
            rng.choice("+-"), rng.randint(0, 23), rng.randint(0, 59))))
        assert mapping._iso_millis(s) is not None, s
        assert outcome(parse_date_millis, s) == outcome(format_loop_only, s), s
