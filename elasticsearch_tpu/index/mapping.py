"""Document mapping: schema, field types, document parsing, dynamic mapping.

Reference analog: index/mapper/ (MapperService.java, DocumentMapper.java,
DocumentMapperParser.java, core/ type mappers, internal/ metadata fields).

TPU-first deviation: a parsed document does not become a Lucene Document;
it becomes columnar contributions — term lists per analyzed text field,
ordinal values per keyword field, numeric/date/bool doc values — that the
segment builder (index/segment.py) packs into device tensors. Metadata
fields collapse to what the columnar engine needs: _id (host dict),
_source (host bytes), _version (host int array); _field_names becomes the
per-column exists bitmask.
"""

from __future__ import annotations

import datetime as _dt
import functools
import json
import numbers
import re
import threading
from dataclasses import dataclass, field

from ..utils.errors import (ElasticsearchTpuError, IllegalArgumentError,
                            MapperParsingError)
from ..utils.settings import Settings
from .analysis import AnalysisService, Analyzer

# ---------------------------------------------------------------------------
# Field types
# ---------------------------------------------------------------------------

TEXT = "text"          # analyzed full-text -> postings (reference: string/analyzed)
KEYWORD = "keyword"    # not-analyzed -> ordinal column (reference: string/not_analyzed)
LONG = "long"
INTEGER = "integer"
SHORT = "short"
BYTE = "byte"
DOUBLE = "double"
FLOAT = "float"
DATE = "date"
BOOLEAN = "boolean"
IP = "ip"

DENSE_VECTOR = "dense_vector"  # [dims] float embedding -> device matrix
GEO_POINT = "geo_point"        # (lat, lon) -> two float32 device columns
                               # (ref: index/mapper/geo/GeoPointFieldMapper)
                               # (MXU-batched exact kNN; no CPU-era ANN
                               # graph needed at these batch sizes)
GEO_SHAPE = "geo_shape"        # GeoJSON shapes -> prefix-tree cell tokens
                               # in standard postings (ops/geo_shape.py;
                               # ref: index/mapper/geo/GeoShapeFieldMapper)

NUMERIC_TYPES = {LONG, INTEGER, SHORT, BYTE, DOUBLE, FLOAT}
JOIN = "join"                  # parent/child relation column (replaces the
                               # reference's per-type _parent metadata field,
                               # index/mapper/internal/ParentFieldMapper.java;
                               # modern join-field shape since this framework
                               # is single-doc-type)

COMPLETION = "completion"      # suggest dictionary entries: host-resident
                               # per-segment input->entry lists (ref:
                               # index/mapper/core/CompletionFieldMapper.java
                               # + the FST-backed
                               # search/suggest/completion/ postings format;
                               # suggest never touches the device)

ALL_TYPES = NUMERIC_TYPES | {TEXT, KEYWORD, DATE, BOOLEAN, IP, DENSE_VECTOR,
                             GEO_POINT, GEO_SHAPE, JOIN, COMPLETION}

# reference "string" type maps by `index` attribute (analyzed|not_analyzed),
# ref: index/mapper/core/StringFieldMapper.java
_LEGACY_STRING = "string"

_EPOCH = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
_SCALARS = (str, int, float, bool)    # by exact type: what JSON decodes to

# json.dumps(obj, separators=(",", ":")) builds an encoder a call
_compact_json = json.JSONEncoder(separators=(",", ":")).encode

_DATE_FORMATS = (
    "%Y-%m-%dT%H:%M:%S.%f%z", "%Y-%m-%dT%H:%M:%S%z",
    "%Y-%m-%dT%H:%M:%S.%f", "%Y-%m-%dT%H:%M:%S",
    "%Y-%m-%d %H:%M:%S", "%Y-%m-%d", "%d/%b/%Y:%H:%M:%S %z",
)


_EPOCH_ORDINAL = _dt.date(1970, 1, 1).toordinal()
_EPOCH_DIGITS = re.compile(r"[+-]?\d{10,}")
_ISO_DATE_TIME = re.compile(
    r"(\d{4})-(\d\d)-(\d\d)T(\d\d):(\d\d):(\d\d)(?:\.(\d{1,6}))?"
    r"(?:Z|([+-])(\d\d):(\d\d))?", re.ASCII)


def _iso_millis(s: str) -> int | None:
    """Epoch millis of a fixed-layout ISO-8601 date-time,
    `YYYY-MM-DDTHH:MM:SS[.f{1,6}][Z|+hh:mm|-hh:mm]`; None for anything
    else, which then goes through `_DATE_FORMATS`. It takes only what
    that loop takes, and computes `int(dt.timestamp() * 1000)` with the
    same float arithmetic, so every value parses to the same millis
    either way (tests/test_date_fast_path.py holds both to it)."""
    m = _ISO_DATE_TIME.fullmatch(s)
    if m is None:
        return None
    year, month, day, hh, mm, ss, frac, sign, oh, om = m.groups()
    hh, mm, ss = int(hh), int(mm), int(ss)
    if hh > 23 or mm > 59 or ss > 59:
        return None
    try:
        days = _dt.date(int(year), int(month), int(day)).toordinal() \
            - _EPOCH_ORDINAL
    except ValueError:
        return None
    secs = days * 86400 + hh * 3600 + mm * 60 + ss
    if sign is not None:
        oh, om = int(oh), int(om)
        if oh > 23 or om > 59:
            return None
        off = oh * 3600 + om * 60
        secs -= off if sign == "+" else -off
    if frac is None or not int(frac):
        return secs * 1000
    # datetime.timestamp() is (dt - epoch).total_seconds(): whole
    # microseconds over 10**6 as one true division
    micros = int(frac) * 10 ** (6 - len(frac))
    return int((secs * 10 ** 6 + micros) / 10 ** 6 * 1000)


def _format_loop_millis(s: str, value) -> int:
    for fmt in _DATE_FORMATS:
        try:
            dt = _dt.datetime.strptime(s, fmt)
            if dt.tzinfo is None:
                dt = dt.replace(tzinfo=_dt.timezone.utc)
            return int(dt.timestamp() * 1000)
        except ValueError:
            continue
    raise MapperParsingError(f"failed to parse date value [{value}]")


def parse_date_millis(value) -> int:
    """Parse a date value to epoch millis.

    Ref: index/mapper/core/DateFieldMapper.java (joda `dateOptionalTime
    || epoch_millis`). Accepts epoch millis ints, ISO-8601 strings, and
    the common-log format used by the http_logs benchmark corpus.
    """
    if type(value) is not str:
        if isinstance(value, bool):
            raise MapperParsingError(
                f"cannot parse boolean [{value}] as date")
        if isinstance(value, numbers.Number):
            return int(value)
    s = str(value).strip()
    millis = _iso_millis(s)
    if millis is not None:
        return millis
    if _EPOCH_DIGITS.fullmatch(s):
        return int(s)
    return _format_loop_millis(s, value)


def format_date_millis(millis: int) -> str:
    dt = _EPOCH + _dt.timedelta(milliseconds=int(millis))
    return dt.strftime("%Y-%m-%dT%H:%M:%S.") + f"{dt.microsecond // 1000:03d}Z"


_IP_RE = re.compile(r"^(\d{1,3})\.(\d{1,3})\.(\d{1,3})\.(\d{1,3})$")


def parse_ip(value) -> int:
    """IPv4 -> uint32 (stored as a numeric column, like the reference's
    IpFieldMapper which indexes IPs as longs)."""
    if type(value) is not str and isinstance(value, numbers.Number) \
            and not isinstance(value, bool):
        return int(value)
    m = _IP_RE.match(str(value))
    if not m:
        raise MapperParsingError(f"failed to parse ip [{value}]")
    a, b, c, d = map(int, m.groups())
    if a > 255 or b > 255 or c > 255 or d > 255:
        raise MapperParsingError(f"failed to parse ip [{value}]")
    return (a << 24) | (b << 16) | (c << 8) | d


def _geo_precision_chars(precision) -> int:
    """Geo-context precision -> geohash length: bare ints are geohash
    chars; distance strings pick the finest level whose cell still covers
    the distance (ref: GeoUtils.geoHashLevelsForPrecision)."""
    if precision is None:
        return 12
    if isinstance(precision, int):
        return max(1, min(12, precision))
    from ..ops.geo import parse_distance
    meters = parse_distance(precision)
    # approximate geohash cell heights in meters per level
    sizes = [5_009_400, 1_252_300, 156_500, 39_100, 4_890, 1_220,
             153, 38, 4.8, 1.2, 0.15, 0.037]
    for level, size in enumerate(sizes, start=1):
        if size <= meters:
            return level
    return 12


def _parse_shape_config(spec: dict) -> dict:
    """geo_shape mapping params -> normalized config (ref:
    GeoShapeFieldMapper.Builder: tree geohash|quadtree, tree_levels or
    precision distance, distance_error_pct default 0.025)."""
    from ..ops.geo_shape import make_tree
    tree_name = str(spec.get("tree", "geohash"))
    tree = make_tree(tree_name)  # validates the name
    cfg: dict = {"tree": tree_name}
    if spec.get("tree_levels") is not None:
        cfg["tree_levels"] = int(spec["tree_levels"])
    elif spec.get("precision") is not None:
        from ..ops.geo import parse_distance
        cfg["precision"] = str(spec["precision"])
        cfg["tree_levels"] = tree.levels_for_meters(
            parse_distance(spec["precision"]))
    else:
        cfg["tree_levels"] = tree.levels_for_meters(50.0)  # default "50m"
    cfg["distance_error_pct"] = float(
        spec.get("distance_error_pct", 0.025))
    return cfg


def shape_tree_config(fm: "FieldMapper"):
    """(tree, tree_levels, distance_error_pct) for a geo_shape field."""
    from ..ops.geo_shape import make_tree
    cfg = fm.shape or {}
    tree = make_tree(cfg.get("tree", "geohash"))
    levels = int(cfg.get("tree_levels") or tree.levels_for_meters(50.0))
    return tree, min(levels, tree.max_levels_cap), \
        float(cfg.get("distance_error_pct", 0.025))


@dataclass
class FieldMapper:
    """One field's schema entry. Ref: index/mapper/FieldMapper.java."""

    name: str
    type: str
    analyzer: str = "standard"
    search_analyzer: str | None = None
    index: bool = True          # ref: "index" attribute (no|analyzed|not_analyzed)
    doc_values: bool = True     # numeric/keyword/date columns resident on device
    store: bool = False
    boost: float = 1.0
    fmt: str | None = None      # date format hint
    ignore_malformed: bool = False
    dims: int | None = None     # dense_vector dimensionality
    similarity: str = "cosine"  # dense_vector: cosine|dot_product|l2_norm;
                                # text: similarity NAME resolved by
                                # index/similarity.py ("" = index default)
    relations: dict | None = None  # join: parent relation -> child(s)
    legacy_string: bool = False    # declared as 2.0 "string": echo it back
    context: dict | None = None    # completion: context mapping config
                                   # (ref: suggest/context/ContextMapping)
    shape: dict | None = None      # geo_shape: {tree, tree_levels,
                                   # precision, distance_error_pct}
                                   # (ref: GeoShapeFieldMapper.Builder)

    def to_dict(self) -> dict:
        if self.legacy_string:
            d: dict = {"type": "string"}
            if self.type == KEYWORD:
                d["index"] = "not_analyzed"
            if self.type == TEXT and self.analyzer != "standard":
                d["analyzer"] = self.analyzer
            if self.boost != 1.0:
                d["boost"] = self.boost
            if self.type == TEXT and self.similarity not in ("", "cosine"):
                d["similarity"] = self.similarity
            return d
        d: dict = {"type": self.type}
        if self.type == TEXT and self.analyzer != "standard":
            d["analyzer"] = self.analyzer
        if self.type == TEXT and self.similarity not in ("", "cosine"):
            d["similarity"] = self.similarity
        if not self.index:
            d["index"] = False
        if self.boost != 1.0:
            d["boost"] = self.boost
        if self.type == DENSE_VECTOR:
            d["dims"] = self.dims
            d["similarity"] = self.similarity
        if self.type == JOIN:
            d["relations"] = self.relations or {}
        if self.type == COMPLETION and self.context:
            d["context"] = self.context
        if self.type == GEO_SHAPE and self.shape:
            d.update(self.shape)
        return d


@dataclass(slots=True)
class ParsedField:
    """Columnar contribution of one field of one document."""

    name: str
    type: str
    tokens: list[str] | None = None   # TEXT: analyzed terms (postings input)
    value: object = None              # KEYWORD: str; numeric/date/bool/ip: number


@dataclass(slots=True)
class ParsedDocument:
    """Ref: index/mapper/ParsedDocument.java — but columnar. `nested`
    carries block-join sub-documents (ref: ParsedDocument.docs() — Lucene
    indexes nested objects as adjacent hidden docs before their parent):
    (path, fields, source_bytes) per nested object occurrence."""

    doc_id: str
    source: bytes
    fields: list[ParsedField] = field(default_factory=list)
    nested: list[tuple] = field(default_factory=list)


class DocumentMapper:
    """Schema for one index: field name -> FieldMapper; parses JSON docs.

    Ref: index/mapper/DocumentMapper.java + DocumentMapperParser.java.
    The reference's per-type mappings (doc types) were removed in later ES;
    we are single-type per index (type name kept only for API compat).
    """

    def __init__(self, analysis: AnalysisService, mapping: dict | None = None,
                 dynamic: bool = True):
        self.analysis = analysis
        self.dynamic = dynamic
        self._fields: dict[str, FieldMapper] = {}
        self._multi_fields: dict[str, list[str]] = {}  # parent -> sub names
        self._nested_paths: set[str] = set()
        # what the mapping decides about a field, decided once and not
        # once a value: name -> its emitter (_emitter_for), and name ->
        # the emitters a scalar value of a mapped leaf goes through
        # (_leaf_plan). Emptied by _mapping_changed.
        self._emitters: dict[str, object] = {}
        self._leaf_plans: dict[str, tuple] = {}
        self._plan_lock = threading.Lock()
        # counts the mapping's changes (_mapping_changed): what a reader
        # keeps of a parsed and bound body is keyed by it
        self.version = 0
        self.parent_type: str | None = None
        self.routing_required = False
        self.ts_enabled = False
        self.ttl_enabled = False
        self.ttl_default_ms: int | None = None
        if mapping:
            self._parse_mapping(mapping)

    # -- schema ------------------------------------------------------------
    _META_KEYS = frozenset((
        "dynamic", "properties", "_meta", "_source", "_all", "_routing",
        "_parent", "_timestamp", "_ttl", "_size", "date_detection",
        "numeric_detection", "dynamic_templates", "dynamic_date_formats"))

    def _parse_mapping(self, mapping: dict) -> None:
        if "_timestamp" in mapping and isinstance(mapping["_timestamp"],
                                                  dict):
            # ref: index/mapper/internal/TimestampFieldMapper.java
            self.ts_enabled = bool(mapping["_timestamp"].get("enabled"))
        if "_ttl" in mapping and isinstance(mapping["_ttl"], dict):
            # ref: index/mapper/internal/TTLFieldMapper.java (default
            # ttl applies when the write supplies none)
            self.ttl_enabled = bool(mapping["_ttl"].get("enabled"))
            dflt = mapping["_ttl"].get("default")
            if dflt is not None:
                from ..utils.settings import parse_time_value
                self.ttl_default_ms = parse_time_value(dflt, 0)
        if "_parent" in mapping and isinstance(mapping["_parent"], dict):
            # _parent declares the parent type; children route by parent
            # id (ref: index/mapper/internal/ParentFieldMapper.java)
            self.parent_type = mapping["_parent"].get("type")
        if "_routing" in mapping and isinstance(mapping["_routing"], dict):
            self.routing_required = bool(
                mapping["_routing"].get("required", False))
        if "dynamic" in mapping:
            dyn = mapping["dynamic"]
            if isinstance(dyn, bool):
                self.dynamic = dyn
            elif str(dyn).lower() == "strict":
                self.dynamic = "strict"
            else:
                self.dynamic = str(dyn).lower() != "false"
        if "properties" in mapping:
            props = mapping["properties"]
        else:
            # bare form: treat non-meta keys as field specs
            props = {k: v for k, v in mapping.items() if k not in self._META_KEYS}
        if not isinstance(props, dict):
            raise MapperParsingError("mapping [properties] must be an object")
        try:
            for name, spec in props.items():
                self._add_field(name, spec)
        finally:
            self._mapping_changed()

    def _mapping_changed(self) -> None:
        """After the last write to `_fields` / `_multi_fields` /
        `_nested_paths`: what was decided from the old mapping goes."""
        with self._plan_lock:
            self._emitters.clear()
            self._leaf_plans.clear()
            self.version += 1

    def _add_field(self, name: str, spec: dict) -> FieldMapper:
        if not isinstance(spec, dict):
            raise MapperParsingError(f"mapping for field [{name}] must be an object")
        if spec.get("type") == "nested":
            # nested object: children become block-join sub-documents
            # (ref: index/mapper/object/ObjectMapper.java Nested)
            self._nested_paths.add(name)
            for child, child_spec in (spec.get("properties") or {}).items():
                self._add_field(f"{name}.{child}", child_spec)
            return None  # type: ignore[return-value]
        if "properties" in spec and spec.get("type") in (None, "object"):
            # object field: flatten children as dotted names
            # (ref: index/mapper/object/ObjectMapper.java)
            for child, child_spec in spec["properties"].items():
                self._add_field(f"{name}.{child}", child_spec)
            return None  # type: ignore[return-value]
        typ = spec.get("type")
        if typ == "multi_field":
            # legacy multi_field (ref: index/mapper/core/
            # TypeParsers.parseMultiField legacy path): the sub-field
            # named like the parent is the primary; others are subs
            subs = dict(spec.get("fields") or {})
            primary = subs.pop(name.rsplit(".", 1)[-1], None)
            spec = dict(primary) if primary else {"type": "string"}
            spec["fields"] = subs
            typ = spec.get("type")
        if typ == JOIN and not isinstance(spec.get("relations"), dict):
            raise MapperParsingError(
                f"join field [{name}] requires a [relations] object")
        legacy_string = typ == _LEGACY_STRING
        if legacy_string:
            typ = KEYWORD if spec.get("index") == "not_analyzed" else TEXT
        if typ not in ALL_TYPES:
            raise MapperParsingError(f"no handler for type [{typ}] declared on field [{name}]")
        idx = spec.get("index", True)
        fm = FieldMapper(
            name=name, type=typ,
            analyzer=spec.get("analyzer", "standard"),
            search_analyzer=spec.get("search_analyzer"),
            index=idx not in (False, "no", "none"),
            doc_values=bool(spec.get("doc_values", True)),
            store=bool(spec.get("store", False)),
            boost=float(spec.get("boost", 1.0)),
            fmt=spec.get("format"),
            ignore_malformed=bool(spec.get("ignore_malformed", False)),
            dims=(int(spec["dims"]) if spec.get("dims") is not None else None),
            similarity=str(spec.get("similarity", "cosine")),
            relations=(dict(spec["relations"]) if typ == JOIN else None),
            legacy_string=legacy_string,
            shape=(_parse_shape_config(spec) if typ == GEO_SHAPE else None),
            context=(dict(spec["context"])
                     if typ == COMPLETION and isinstance(
                         spec.get("context"), dict) else None),
        )
        # multi-fields: {"fields": {"keyword": {"type": "keyword"}}} ->
        # sub-mapper at "<name>.<sub>" (ref: core/AbstractFieldMapper multiFields)
        for sub_name, sub_spec in (spec.get("fields") or {}).items():
            sub = self._add_field(f"{name}.{sub_name}", sub_spec)
            if sub is not None:
                self._multi_fields.setdefault(name, []).append(sub.name)
        existing = self._fields.get(name)
        if existing:
            # ref: merge conflict detection, index/mapper/MergeContext.java
            if existing.type != fm.type:
                raise MapperParsingError(
                    f"mapper [{name}] of different type, current_type "
                    f"[{existing.type}], merged_type [{fm.type}]")
            if existing.type == TEXT and existing.analyzer != fm.analyzer:
                raise MapperParsingError(
                    f"mapper [{name}] has different [analyzer]: "
                    f"[{existing.analyzer}] vs [{fm.analyzer}]")
            if existing.type == TEXT:
                # impacts are baked at index time (index/similarity.py),
                # so similarity is as immutable as the analyzer; a re-put
                # that omits it inherits the existing choice ("cosine" is
                # the unset sentinel shared with dense_vector)
                if fm.similarity in ("", "cosine"):
                    fm.similarity = existing.similarity
                else:
                    old = existing.similarity
                    # unset means the engine default; explicitly naming
                    # that default is not a change
                    if old in ("", "cosine"):
                        old = "BM25"
                    if old != fm.similarity and not (
                            old in ("BM25", "bm25")
                            and fm.similarity in ("BM25", "bm25")):
                        raise MapperParsingError(
                            f"mapper [{name}] has different [similarity]")
            if existing.index != fm.index:
                raise MapperParsingError(
                    f"mapper [{name}] has different [index] values")
        self._fields[name] = fm
        if "." in name:
            # a dotted leaf whose parent is itself a leaf field is a
            # multi-field (e.g. "s.keyword" under text "s") — re-link it
            # so values flow from the parent. This matters when mappings
            # round-trip flattened through the cluster-state side channel.
            parent = name.rsplit(".", 1)[0]
            if parent in self._fields:
                links = self._multi_fields.setdefault(parent, [])
                if name not in links:
                    links.append(name)
        return fm

    def merge(self, mapping: dict) -> None:
        """Merge an additional mapping (PUT _mapping); conflicts raise."""
        self._parse_mapping(mapping)

    def field(self, name: str) -> FieldMapper | None:
        return self._fields.get(name)

    @property
    def fields(self) -> dict[str, FieldMapper]:
        return dict(self._fields)

    def to_dict(self) -> dict:
        sub_names = {s for subs in self._multi_fields.values()
                     for s in subs}
        props = {}
        for n, f in sorted(self._fields.items()):
            if n in sub_names:
                continue  # multi-field subs render under parent "fields"
            d = f.to_dict()
            subs = self._multi_fields.get(n)
            if subs:
                d["fields"] = {
                    s.rsplit(".", 1)[-1]: self._fields[s].to_dict()
                    for s in sorted(subs) if s in self._fields}
            props[n] = d
        for path in sorted(self._nested_paths):
            props[path] = {"type": "nested"}
        return {"properties": props}

    # -- document parsing --------------------------------------------------
    def _dynamic_type(self, name: str, value) -> str:
        """Infer a field type from a JSON value.

        Ref: dynamic mapping in index/mapper/object/ObjectMapper.java
        (serializeValue): bool->boolean, int->long, float->double,
        date-parseable string->date, else string(text).
        """
        if isinstance(value, bool):
            return BOOLEAN
        if isinstance(value, int):
            return LONG
        if isinstance(value, float):
            return DOUBLE
        s = str(value)
        try:
            parse_date_millis(s)
            if re.match(r"^\d{4}-\d{2}-\d{2}", s) or re.match(r"^\d{2}/[A-Za-z]{3}/\d{4}", s):
                return DATE
        except MapperParsingError:
            pass
        return TEXT

    @staticmethod
    def _converter(fm: FieldMapper):
        """`convert(value)`: a value of this date, boolean, ip or
        numeric field as its column holds it, or MapperParsingError /
        ValueError / TypeError."""
        typ = fm.type
        if typ == DATE:
            return parse_date_millis
        if typ == BOOLEAN:
            return lambda value: value if isinstance(value, bool) \
                else str(value).lower() in ("true", "1", "on", "yes")
        if typ == IP:
            return parse_ip
        if typ in (LONG, INTEGER, SHORT, BYTE):
            def to_int(value):
                if isinstance(value, str) \
                        and not value.strip().lstrip("+-").isdigit():
                    raise MapperParsingError(
                        f"failed to parse [{fm.name}] as {typ}: [{value}]")
                return int(value)
            return to_int
        return float        # DOUBLE, FLOAT

    def parse(self, doc_id: str, source: dict | bytes | str) -> ParsedDocument:
        """JSON document -> columnar field contributions."""
        parsed = self.parse_many([(doc_id, source)])[0]
        if isinstance(parsed, ElasticsearchTpuError):
            raise parsed
        return parsed

    def parse_many(self, docs: list[tuple[str, dict | bytes | str]]
                   ) -> list[ParsedDocument | ElasticsearchTpuError]:
        """Parse a batch in document order (a dynamic field maps from
        the first document that carries it, as one at a time). A
        document that fails is its own error in the result (a
        MapperParsingError, or what an unknown analyzer raises) and the
        batch goes on. Text values are not analyzed during the
        walk: each waits as (analyzer, field to fill, text), and the
        documents that parsed go to each analyzer together at the end,
        one `analyze_batch` call for the whole batch."""
        out: list[ParsedDocument | ElasticsearchTpuError] = []
        waiting: dict[int, tuple[Analyzer, list[ParsedField], list[str]]] = {}
        for doc_id, source in docs:
            texts: list[tuple[Analyzer, ParsedField, str]] = []
            try:
                out.append(self._parse_one(doc_id, source, texts))
            except ElasticsearchTpuError as e:
                out.append(e)
                continue
            for analyzer, pf, text in texts:
                _a, fields, strings = waiting.setdefault(
                    id(analyzer), (analyzer, [], []))
                fields.append(pf)
                strings.append(text)
        for analyzer, fields, strings in waiting.values():
            for pf, tokens in zip(fields, analyzer.analyze_batch(strings)):
                pf.tokens = tokens
        return out

    def _parse_one(self, doc_id: str, source: dict | bytes | str,
                   texts: list) -> ParsedDocument:
        if isinstance(source, (bytes, str)):
            raw = source if isinstance(source, bytes) else source.encode()
            try:
                obj = json.loads(source)
            except json.JSONDecodeError as e:
                raise MapperParsingError(f"failed to parse document: {e}")
        else:
            obj = source
            raw = _compact_json(source).encode()
        if not isinstance(obj, dict):
            raise MapperParsingError("document root must be an object")
        out = ParsedDocument(doc_id=doc_id, source=raw)
        self._parse_object("", obj, out, texts)
        self._resolve_completion_contexts(obj, out)
        return out

    def _resolve_completion_contexts(self, obj: dict,
                                     out: ParsedDocument) -> None:
        """Fill each completion entry's context values from the entry
        itself, a doc-field `path`, or the mapping `default` — in that
        order (ref: search/suggest/context/CategoryContextMapping
        parseContext + GeolocationContextMapping)."""
        for pf in out.fields:
            if pf.type != COMPLETION:
                continue
            fm = self._fields.get(pf.name)
            if fm is None or not fm.context:
                continue
            entry = pf.value
            supplied = entry.get("context") or {}
            resolved: dict = {}
            for ctx_name, cfg in fm.context.items():
                v = supplied.get(ctx_name)
                if v is None and cfg.get("path"):
                    v = obj
                    for part in str(cfg["path"]).split("."):
                        v = v.get(part) if isinstance(v, dict) else None
                        if v is None:
                            break
                if v is None:
                    v = cfg.get("default")
                if v is None:
                    continue
                if cfg.get("type") == "geo":
                    from ..ops.geo import parse_geo_point, geohash_encode
                    prec = _geo_precision_chars(cfg.get("precision"))
                    lat, lon = parse_geo_point(v)
                    resolved[ctx_name] = geohash_encode(lat, lon, prec)
                else:
                    vals = v if isinstance(v, list) else [v]
                    resolved[ctx_name] = [str(x) for x in vals]
            entry["context"] = resolved

    def _parse_object(self, prefix: str, obj: dict, out: ParsedDocument,
                      texts: list) -> None:
        plans = self._leaf_plans
        for key, value in obj.items():
            name = f"{prefix}{key}"
            if type(value) in _SCALARS:
                plan = plans.get(name)
                if plan is None:
                    plan = self._leaf_plan(name)
                if plan is not None:
                    for emit in plan:
                        emit(value, out, texts)
                    continue
            if name in self._nested_paths:
                # each element becomes a block-join sub-document (ref:
                # ObjectMapper nested=true -> Lucene child docs). Doubly-
                # nested children attach to the root doc, distinguished
                # by their full path.
                elements = value if isinstance(value, list) else [value]
                for el in elements:
                    if not isinstance(el, dict):
                        raise MapperParsingError(
                            f"nested field [{name}] elements must be objects")
                    src = _compact_json(el).encode()
                    sub = ParsedDocument(doc_id="", source=src)
                    self._parse_object(f"{name}.", el, sub, texts)
                    out.nested.append((name, sub.fields, src))
                    out.nested.extend(sub.nested)
                continue
            if isinstance(value, dict):
                fm = self._fields.get(name)
                if fm is not None and fm.type in (GEO_POINT, GEO_SHAPE,
                                                  JOIN, COMPLETION):
                    # {"lat":..,"lon":..} point / GeoJSON shape / join /
                    # completion entry, not a sub-object
                    self._parse_value(name, value, out, texts)
                    continue
                self._parse_object(f"{name}.", value, out, texts)
                continue
            if isinstance(value, list):
                fm = self._fields.get(name)
                if fm is not None and fm.type == DENSE_VECTOR:
                    self._parse_value(name, value, out, texts)
                    continue
                if fm is not None and fm.type == GEO_POINT and value and \
                        isinstance(value[0], (int, float)):
                    # bare [lon, lat] pair (GeoJSON order)
                    self._parse_value(name, value, out, texts)
                    continue
            values = value if isinstance(value, list) else [value]
            for v in values:
                if v is None:
                    continue
                if isinstance(v, dict):
                    fm = self._fields.get(name)
                    if fm is not None and fm.type in (GEO_POINT, GEO_SHAPE):
                        self._parse_value(name, v, out, texts)  # point/shape array
                    else:
                        self._parse_object(f"{name}.", v, out, texts)
                    continue
                self._parse_value(name, v, out, texts)

    def _parse_value(self, name: str, value, out: ParsedDocument,
                     texts: list) -> None:
        fm = self._fields.get(name)
        if fm is None:
            if self.dynamic == "strict":
                # ref: StrictDynamicMappingException (400)
                raise MapperParsingError(
                    f"mapping set to strict, dynamic introduction of [{name}] "
                    f"within [_doc] is not allowed")
            if not self.dynamic:
                return  # dynamic=false ignores unknown fields (ref behavior)
            fm = FieldMapper(name=name, type=self._dynamic_type(name, value))
            self._fields[name] = fm
            if fm.type == TEXT:
                # dynamic strings get a keyword twin (modern ES dynamic
                # template default: text + .keyword sub-field) so terms
                # aggs and sorts work out of the box
                twin = FieldMapper(name=f"{name}.keyword", type=KEYWORD)
                self._fields[twin.name] = twin
                self._multi_fields.setdefault(name, []).append(twin.name)
            self._mapping_changed()
        self._emit_field(fm, value, out, texts)
        # multi-fields index the same value under each sub-mapper's type
        # (ref: AbstractFieldMapper.MultiFields.parse)
        for sub_name in self._multi_fields.get(name, ()):
            sub = self._fields.get(sub_name)
            if sub is not None:
                self._emit_field(sub, value, out, texts)

    def _leaf_plan(self, name: str) -> tuple | None:
        """The emitters a scalar value under `name` goes through: the
        field's own, then each multi-field's, as `_parse_value` calls
        them. None where the mapping has no such leaf (unmapped, so
        `_parse_value` decides on `dynamic`; or a nested path)."""
        with self._plan_lock:
            fm = self._fields.get(name)
            if fm is None or name in self._nested_paths:
                return None
            subs = (self._fields.get(sub)
                    for sub in self._multi_fields.get(name, ()))
            plan = tuple(
                emit for emit in (
                    self._emitter_for(f) for f in (fm, *subs)
                    if f is not None)
                if emit is not None)
            self._leaf_plans[name] = plan
            return plan

    def _emit_field(self, fm: FieldMapper, value, out: ParsedDocument,
                    texts: list) -> None:
        try:
            emit = self._emitters[fm.name]
        except KeyError:
            with self._plan_lock:
                emit = self._emitter_for(fm)
        if emit is not None:
            emit(value, out, texts)

    def _emitter_for(self, fm: FieldMapper):
        """`emit(value, out, texts)`: add what one value of this field
        contributes to `out.fields`; None where the field contributes
        nothing. Caller holds `_plan_lock`."""
        name = fm.name
        try:
            return self._emitters[name]
        except KeyError:
            pass
        if self._fields.get(name) is not fm:
            return self._make_emitter(fm)     # not this mapping's: uncached
        emit = self._emitters[name] = self._make_emitter(fm)
        return emit

    def _make_emitter(self, fm: FieldMapper):
        name, typ = fm.name, fm.type
        if typ == TEXT:
            if not fm.index:
                return None  # index:false text is neither searchable nor columnar

            # an unknown analyzer fails the document, as it did
            analyzer = self.analysis.analyzer(fm.analyzer)

            def emit_text(value, out, texts):
                pf = ParsedField(name, TEXT)
                out.fields.append(pf)
                texts.append((analyzer, pf, str(value)))
            return emit_text
        if typ == COMPLETION:
            return functools.partial(self._emit_completion, fm)
        if not fm.index and not fm.doc_values:
            return None
        if typ == KEYWORD:
            sub_field = "." in name     # ignore_above on subs

            def emit_keyword(value, out, texts):
                s = str(value)
                if len(s) <= 256 or not sub_field:
                    out.fields.append(ParsedField(name, KEYWORD, None, s))
            return emit_keyword
        structured = {JOIN: self._emit_join, GEO_POINT: self._emit_geo_point,
                      GEO_SHAPE: self._emit_geo_shape,
                      DENSE_VECTOR: self._emit_dense_vector}.get(typ)
        if structured is not None:
            return functools.partial(structured, fm)
        convert = self._converter(fm)

        def emit_converted(value, out, texts):
            try:
                converted = convert(value)
            except (ValueError, TypeError, MapperParsingError) as e:
                if fm.ignore_malformed:
                    return
                if isinstance(e, MapperParsingError):
                    raise
                raise MapperParsingError(
                    f"failed to parse [{name}] value [{value}]")
            out.fields.append(ParsedField(name, typ, None, converted))
        return emit_converted

    @staticmethod
    def _emit_completion(fm: FieldMapper, value, out: ParsedDocument,
                         texts: list) -> None:
        # string | [strings] | {"input": ..., "output": ..., "weight":
        # ..., "payload": ..., "context": ...} -> one normalized entry
        # (ref: CompletionFieldMapper.parse)
        if isinstance(value, dict):
            inputs = value.get("input") or []
            inputs = inputs if isinstance(inputs, list) else [inputs]
            entry = {
                "input": [str(i) for i in inputs],
                "output": (str(value["output"])
                           if value.get("output") is not None else None),
                "weight": int(value.get("weight", 1)),
                "payload": value.get("payload"),
                "context": (value.get("context")
                            if isinstance(value.get("context"), dict)
                            else {}),
            }
        else:
            entry = {"input": [str(value)], "output": None,
                     "weight": 1, "payload": None, "context": {}}
        out.fields.append(ParsedField(name=fm.name, type=COMPLETION,
                                      value=entry))

    @staticmethod
    def _emit_join(fm: FieldMapper, value, out: ParsedDocument,
                   texts: list) -> None:
        # {"name": relation, "parent": id} or bare relation string ->
        # relation ordinal column + "<field>#parent" id column (the
        # reference's _parent field data, ParentFieldMapper.java)
        if isinstance(value, dict):
            rel = value.get("name")
            parent = value.get("parent")
        else:
            rel, parent = str(value), None
        known = set()
        for p, c in (fm.relations or {}).items():
            known.add(p)
            known.update(c if isinstance(c, list) else [c])
        if rel not in known:
            raise MapperParsingError(
                f"unknown join relation [{rel}] on field [{fm.name}]")
        out.fields.append(ParsedField(name=fm.name, type=KEYWORD,
                                      value=str(rel)))
        if parent is not None:
            out.fields.append(ParsedField(name=f"{fm.name}#parent",
                                          type=KEYWORD,
                                          value=str(parent)))

    @staticmethod
    def _emit_geo_point(fm: FieldMapper, value, out: ParsedDocument,
                        texts: list) -> None:
        from ..ops.geo import parse_geo_point
        from ..utils.errors import QueryParsingError
        try:
            lat, lon = parse_geo_point(value)
        except QueryParsingError as e:
            if fm.ignore_malformed:
                return
            raise MapperParsingError(str(e))
        out.fields.append(ParsedField(name=fm.name, type=GEO_POINT,
                                      value=(lat, lon)))

    @staticmethod
    def _emit_geo_shape(fm: FieldMapper, value, out: ParsedDocument,
                        texts: list) -> None:
        # GeoJSON -> prefix-tree cell tokens in the standard postings
        # layout, so shape queries are terms disjunctions on device
        # (ops/geo_shape.py; ref: GeoShapeFieldMapper.parse)
        from ..ops.geo_shape import (parse_shape, index_tokens,
                                     effective_levels)
        from ..utils.errors import QueryParsingError
        try:
            shp = parse_shape(value)
            tree, levels, err_pct = shape_tree_config(fm)
            toks = index_tokens(shp, tree,
                                effective_levels(shp, tree, levels,
                                                 err_pct))
        except (QueryParsingError, TypeError, ValueError, IndexError,
                KeyError) as e:
            if fm.ignore_malformed:
                return
            raise MapperParsingError(
                f"failed to parse [{fm.name}]: {e}")
        out.fields.append(ParsedField(name=fm.name, type=TEXT,
                                      tokens=toks))

    @staticmethod
    def _emit_dense_vector(fm: FieldMapper, value, out: ParsedDocument,
                           texts: list) -> None:
        if not isinstance(value, list):
            raise MapperParsingError(
                f"dense_vector [{fm.name}] requires an array of floats")
        vec = [float(x) for x in value]
        if fm.dims is not None and len(vec) != fm.dims:
            raise MapperParsingError(
                f"dense_vector [{fm.name}] has {len(vec)} dims, "
                f"mapping expects {fm.dims}")
        out.fields.append(ParsedField(name=fm.name, type=DENSE_VECTOR,
                                      value=vec))


class MapperService:
    """Per-index mapper registry. Ref: index/mapper/MapperService.java.

    TPU-first deviation: the ENGINE is single-type — one merged field
    space, one columnar layout (`self.mapper`). The reference's per-type
    mappings survive as API metadata: `self.types` keeps one
    DocumentMapper VIEW per declared type, fed by create-index bodies
    and put-mapping calls, rendered by GET _mapping /
    _mapping/field/{fields}. Typed writes parse through the merged
    mapper; dynamic fields introduced by documents appear in the merged
    mapping (the view shows only declared fields)."""

    def __init__(self, index_settings: Settings = Settings.EMPTY,
                 mapping: dict | None = None,
                 type_mappings: dict | None = None):
        self.analysis = AnalysisService(index_settings)
        self.index_settings = index_settings
        self._sim_service = None  # built lazily (index/similarity.py)
        self.mapper = DocumentMapper(self.analysis, mapping)
        self.types: dict[str, DocumentMapper] = {}
        for tname, spec in (type_mappings or {}).items():
            self.put_type_mapping(tname, spec or {})

    def parse(self, doc_id: str, source) -> ParsedDocument:
        return self.mapper.parse(doc_id, source)

    def parse_many(self, docs):
        return self.mapper.parse_many(docs)

    def merge_mapping(self, mapping: dict) -> None:
        self.mapper.merge(mapping)

    @property
    def version(self) -> int:
        """Moves with every change of the merged mapping: a merge
        (PUT _mapping) and a field a document brought."""
        return self.mapper.version

    def put_type_mapping(self, type_name: str, spec: dict) -> None:
        """Merge `spec` into the named type's view AND the engine's
        merged mapper (ref: MetaDataMappingService putMapping +
        DocumentMapper.merge)."""
        view = self.types.get(type_name)
        if view is None:
            self.types[type_name] = DocumentMapper(self.analysis, spec)
        else:
            view.merge(spec)
        self.mapper.merge(spec)

    def type_mapping_dict(self, type_name: str) -> dict:
        view = self.types.get(type_name)
        return view.to_dict() if view is not None else {"properties": {}}

    @property
    def parent_type(self) -> str | None:
        return self.mapper.parent_type

    @property
    def routing_required(self) -> bool:
        return self.mapper.routing_required

    def mapping_dict(self) -> dict:
        return self.mapper.to_dict()

    def field(self, name: str) -> FieldMapper | None:
        return self.mapper.field(name)

    def similarity_for(self, field: str):
        """The Similarity whose impacts are baked into `field`'s postings
        (ref: SimilarityService.similarity(fieldMapper))."""
        from .similarity import SimilarityService
        if self._sim_service is None:
            self._sim_service = SimilarityService(self.index_settings)
        return self._sim_service.for_field(self, field)

    @property
    def nested_paths(self) -> set[str]:
        return set(self.mapper._nested_paths)

    def join_field(self) -> FieldMapper | None:
        """The index's join field, if one is mapped (at most one, as with
        the reference's single _parent per type)."""
        for fm in self.mapper._fields.values():
            if fm.type == JOIN:
                return fm
        return None

    def search_analyzer_for(self, field_name: str) -> Analyzer:
        fm = self.mapper.field(field_name)
        if fm is None or fm.type != TEXT:
            return self.analysis.analyzer("keyword")
        return self.analysis.analyzer(fm.search_analyzer or fm.analyzer)
