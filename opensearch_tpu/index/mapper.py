"""Schema layer: mappings, field types, and JSON document parsing.

Re-designs the reference's mapper package (server/src/main/java/org/opensearch/
index/mapper/MapperService.java, DocumentParser.java, the ~30 FieldMapper
types) for a columnar TPU segment model:

- text fields    → analyzed terms feeding blocked postings (+ field length for norms)
- keyword fields → exact values feeding both postings (term queries) and an
                   ordinal doc-value column (terms aggs, sorting)
- numeric/date/boolean/ip → dense f64/i64 doc-value columns; range/term queries
                   compile to vectorized compares on the column, not postings
- dense/knn vectors → [dims] f32 rows in a matrix column
- metadata fields _id/_source/_routing/_seq_no/_version handled explicitly
  (reference: index/mapper/SourceFieldMapper.java, SeqNoFieldMapper.java)

Dynamic mapping inference mirrors the reference's DocumentParser defaults:
JSON string → text + `.keyword` subfield, integer → long, float → float,
bool → boolean, object → dotted subfields, array → per-element.
"""

from __future__ import annotations

import datetime as _dt
import functools
import ipaddress
import math
import re
from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Tuple

from opensearch_tpu.common.errors import IllegalArgumentError, MapperParsingError
from opensearch_tpu.analysis import AnalysisRegistry, get_default_registry

TEXT_TYPES = {"text", "match_only_text", "search_as_you_type"}
KEYWORD_TYPES = {"keyword", "constant_keyword", "wildcard",
                 # completion fields store their suggestions as exact values;
                 # the suggester walks the ordinal dictionary by prefix
                 "completion", "search_as_you_type"}
NUMERIC_TYPES = {"long", "integer", "short", "byte", "double", "float", "half_float",
                 "scaled_float", "unsigned_long",
                 # mapper-extras rank features are positive floats with doc
                 # values; scoring behavior lives in rank_feature queries
                 "rank_feature"}
DATE_TYPES = {"date", "date_nanos"}
VECTOR_TYPES = {"knn_vector", "dense_vector"}
# late-interaction multi-vector fields (ColBERT-style): one [tokens, dims]
# matrix per doc, scored by the fused MaxSim kernel (ops/maxsim.py)
RANK_VECTOR_TYPES = {"rank_vectors"}
RANK_VECTORS_COMPRESSION = ("none", "pq")
DEFAULT_MAX_TOKENS = 128
BOOL_TYPES = {"boolean"}
IP_TYPES = {"ip"}
RANGE_TYPES = {"integer_range", "long_range", "float_range", "double_range",
               "date_range", "ip_range"}
# range type -> element type of the hidden #lo / #hi bound columns
_RANGE_ELEM = {"integer_range": "integer", "long_range": "long",
               "float_range": "float", "double_range": "double",
               "date_range": "date", "ip_range": "ip"}
# inclusive-bound adjustment step for exclusive gt/lt on discrete elements
_RANGE_STEP = {"integer": 1.0, "long": 1.0, "date": 1.0, "ip": 1.0}
RANGE_UNBOUNDED = 1e308
GEO_TYPES = {"geo_point", "geo_shape"}

_INT_BOUNDS = {
    "byte": (-2 ** 7, 2 ** 7 - 1),
    "short": (-2 ** 15, 2 ** 15 - 1),
    "integer": (-2 ** 31, 2 ** 31 - 1),
    "long": (-2 ** 63, 2 ** 63 - 1),
    "unsigned_long": (0, 2 ** 64 - 1),
}


# Java date-pattern letters a custom `format` may hold, with what
# `strptime` reads them as and the unit (ms) they resolve; month and year
# have no fixed length and round up by the calendar
_JAVA_DATE_TOKENS = {"yyyy": ("%Y", "y"), "uuuu": ("%Y", "y"),
                     "MM": ("%m", "M"), "dd": ("%d", 86_400_000),
                     "HH": ("%H", 3_600_000), "mm": ("%M", 60_000),
                     "ss": ("%S", 1000), "SSS": ("%f", 1)}
_JAVA_DATE_RUN = re.compile(r"'([^']*)'|([A-Za-z])\2*|.", re.S)
_ISO_PREFIX_UNIT = (
    (re.compile(r"\d{4}"), "y"), (re.compile(r"\d{4}-\d{2}"), "M"),
    (re.compile(r"\d{4}-\d{2}-\d{2}"), 86_400_000),
    (re.compile(r"\d{4}-\d{2}-\d{2}[T ]\d{2}"), 3_600_000),
    (re.compile(r"\d{4}-\d{2}-\d{2}[T ]\d{2}:\d{2}"), 60_000),
    (re.compile(r"\d{4}-\d{2}-\d{2}[T ]\d{2}:\d{2}:\d{2}"), 1000))


@functools.lru_cache(maxsize=256)
def _java_date_pattern(pattern: str):
    """(strptime format, finest unit) of a custom Java date pattern
    (`dd/MM/yyyy`, `yyyy-MM-dd HH:mm:ss`), or None for a pattern with a
    letter run this table does not hold: a built-in format's name
    (`strict_date_optional_time`, `epoch_millis`) among them, which the
    ISO path below reads."""
    out, units = [], []
    for m in _JAVA_DATE_RUN.finditer(pattern):
        if m.group(1) is not None:
            out.append(m.group(1).replace("%", "%%"))
        elif m.group(2) is not None:
            tok = _JAVA_DATE_TOKENS.get(m.group(0))
            if tok is None:
                return None
            out.append(tok[0])
            units.append(tok[1])
        else:
            out.append(m.group(0).replace("%", "%%"))
    if not units:
        return None
    # the finest unit the pattern names, wherever it stands (dd/MM/yyyy)
    approx_ms = {"y": 4e10, "M": 3e9}
    return "".join(out), min(units, key=lambda u: approx_ms.get(u, u))


def _end_of_unit(dt: "_dt.datetime", unit) -> int:
    """Epoch ms of the last millisecond of the `unit` that `dt` opens."""
    if unit == "y":
        nxt = dt.replace(year=dt.year + 1)
    elif unit == "M":
        nxt = dt.replace(year=dt.year + dt.month // 12,
                         month=dt.month % 12 + 1)
    else:
        return int(dt.timestamp() * 1000) + int(unit) - 1
    return int(nxt.timestamp() * 1000) - 1


def parse_date_millis(value: Any, fmt: Optional[str] = None,
                      round_up: bool = False) -> int:
    """Parse a date into epoch milliseconds.

    Covers the reference's default `strict_date_optional_time||epoch_millis`
    (index/mapper/DateFieldMapper.java DEFAULT_DATE_TIME_FORMATTER) and
    custom Java patterns of year, month, day, hour, minute, second and
    millisecond (`fmt` may join alternatives with `||`). `round_up`: the
    parts the text leaves out are filled with their last value, not their
    first (a range's `lte` and `gt` bounds: `21/01/2015` is the end of
    that day; DateMathParser's roundUpProperty).
    """
    if isinstance(value, bool):
        raise MapperParsingError(f"failed to parse date field [{value}]")
    if isinstance(value, (int, float)):
        n = int(value)
        return n * 1000 if fmt == "epoch_second" else n
    text = str(value).strip()
    for alt in (fmt or "").split("||"):
        pat = _java_date_pattern(alt) if alt else None
        if pat is None:
            continue
        try:
            dt = _dt.datetime.strptime(text, pat[0]).replace(
                tzinfo=_dt.timezone.utc)
        except ValueError:
            continue
        return _end_of_unit(dt, pat[1]) if round_up \
            else int(dt.timestamp() * 1000)
    if fmt in ("epoch_millis", "epoch_second") or re.fullmatch(r"-?\d{10,}", text):
        try:
            n = int(text)
            return n * 1000 if fmt == "epoch_second" else n
        except ValueError:
            pass
    # ISO-8601 family: yyyy, yyyy-MM, yyyy-MM-dd, with optional time and zone
    t = text.replace("Z", "+00:00")
    for pattern in (None, "%Y-%m", "%Y"):
        try:
            if pattern is None:
                dt = _dt.datetime.fromisoformat(t)
            else:
                dt = _dt.datetime.strptime(t, pattern)
            if dt.tzinfo is None:
                dt = dt.replace(tzinfo=_dt.timezone.utc)
            if round_up:
                for shape, unit in _ISO_PREFIX_UNIT:
                    if shape.fullmatch(text):
                        return _end_of_unit(dt, unit)
            return int(dt.timestamp() * 1000)
        except ValueError:
            continue
    raise MapperParsingError(f"failed to parse date field [{value}] with format "
                             f"[{fmt or 'strict_date_optional_time||epoch_millis'}]")


@functools.lru_cache(maxsize=1 << 16)
def format_date_millis(millis: int) -> str:
    # memoized: histogram renders format the same bucket keys for every
    # query of a dashboard workload
    dt = _dt.datetime.fromtimestamp(millis / 1000.0, tz=_dt.timezone.utc)
    return dt.strftime("%Y-%m-%dT%H:%M:%S.") + f"{dt.microsecond // 1000:03d}Z"


def ip_to_long(value: str) -> int:
    """Encode an IP as a sortable integer (v4 mapped into v6 space)."""
    try:
        addr = ipaddress.ip_address(str(value))
    except ValueError as e:
        raise MapperParsingError(f"'{value}' is not an IP string literal.") from e
    if isinstance(addr, ipaddress.IPv4Address):
        addr = ipaddress.IPv6Address(b"\x00" * 10 + b"\xff\xff" + addr.packed)
    return int(addr)


@dataclass
class MappedFieldType:
    """Per-field schema record answering query/agg/fielddata questions.

    Reference: index/mapper/MappedFieldType.java.
    """
    name: str
    type: str
    analyzer: str = "standard"
    search_analyzer: Optional[str] = None
    index: bool = True
    doc_values: bool = True
    store: bool = False
    fmt: Optional[str] = None            # date format
    scaling_factor: float = 100.0        # scaled_float
    dims: int = 0                        # vectors
    similarity_space: str = "l2"         # vectors: l2 | cosinesimil | innerproduct
    knn_method: str = "exact"            # vectors: exact | ivf (HNSW → IVF on TPU)
    knn_nlist: int = 128                 # ivf: number of centroids
    knn_nprobe: int = 0                  # ivf: default probes (0 → nlist/8)
    max_tokens: int = 0                  # rank_vectors: per-doc token cap
    compression: str = "none"            # rank_vectors: none | pq
    pq_m: int = 0                        # rank_vectors pq: subspace count
    ignore_above: Optional[int] = None   # keyword
    null_value: Any = None
    boost: float = 1.0
    meta: dict = dc_field(default_factory=dict)

    @property
    def is_text(self):
        return self.type in TEXT_TYPES

    @property
    def is_keyword(self):
        return self.type in KEYWORD_TYPES

    @property
    def is_numeric(self):
        return self.type in NUMERIC_TYPES

    @property
    def is_date(self):
        return self.type in DATE_TYPES

    @property
    def is_bool(self):
        return self.type in BOOL_TYPES

    @property
    def is_range(self):
        return self.type in RANGE_TYPES

    @property
    def is_ip(self):
        return self.type in IP_TYPES

    @property
    def is_vector(self):
        return self.type in VECTOR_TYPES

    @property
    def is_rank_vectors(self):
        return self.type in RANK_VECTOR_TYPES

    @property
    def has_ordinals(self):
        """Fields whose doc values are ordinal-encoded strings."""
        return self.is_keyword or self.is_ip or self.is_bool

    def parse_numeric(self, value: Any) -> float:
        """Note: doc-value columns are float64, so integer fields keep exact
        values only up to 2**53 (a documented deviation from Lucene's int64
        doc values); bounds checks below are exact regardless."""
        if isinstance(value, bool):
            raise MapperParsingError(
                f"failed to parse field [{self.name}] of type [{self.type}]: "
                f"boolean value not allowed")
        if self.type in _INT_BOUNDS:
            if isinstance(value, int):
                n = value
            elif isinstance(value, str) and re.fullmatch(r"-?\d+", value.strip()):
                n = int(value.strip())
            else:
                try:
                    num = float(value)
                except (TypeError, ValueError) as e:
                    raise MapperParsingError(
                        f"failed to parse field [{self.name}] of type [{self.type}] "
                        f"value [{value}]") from e
                if math.isnan(num) or math.isinf(num):
                    raise MapperParsingError(
                        f"[{self.type}] supports only finite values, but got [{value}]")
                n = int(num)  # coerce: truncate decimals, matching coerce=true default
            lo, hi = _INT_BOUNDS[self.type]
            if not (lo <= n <= hi):
                raise MapperParsingError(
                    f"Value [{value}] is out of range for a {self.type}")
            return float(n)
        try:
            num = float(value)
        except (TypeError, ValueError) as e:
            raise MapperParsingError(
                f"failed to parse field [{self.name}] of type [{self.type}] "
                f"value [{value}]") from e
        if math.isnan(num) or math.isinf(num):
            raise MapperParsingError(f"[{self.type}] supports only finite values, "
                                     f"but got [{value}]")
        if self.type == "scaled_float":
            return float(round(num * self.scaling_factor)) / self.scaling_factor
        return num

    def to_comparable(self, value: Any) -> float:
        """Convert a user-supplied query value to the doc-value column domain."""
        if self.is_date:
            return float(parse_date_millis(value, self.fmt))
        if self.is_ip:
            return float(ip_to_long(value))
        if self.is_bool:
            return 1.0 if _parse_boolish(value) else 0.0
        return self.parse_numeric(value)


def _parse_boolish(value: Any) -> bool:
    if isinstance(value, bool):
        return value
    text = str(value).strip().lower()
    if text in ("true",):
        return True
    if text in ("false", ""):
        return False
    raise MapperParsingError(f"Failed to parse value [{value}] as only [true] or [false] "
                             f"are allowed.")


@dataclass
class ParsedField:
    """One field's contribution of a parsed document."""
    terms: Optional[List[Tuple[str, int]]] = None  # analyzed (term, position) for text
    length: int = 0                                 # token count for norms
    exact_values: Optional[List[str]] = None        # keyword-style exact terms
    numeric_values: Optional[List[float]] = None    # numeric/date/bool/ip doc values
    vector: Optional[List[float]] = None
    token_vectors: Optional[List[List[float]]] = None  # rank_vectors matrix


@dataclass
class ParsedDocument:
    """Reference: index/mapper/ParsedDocument.java. `children` carries one
    (nested path, fields) entry per nested object — each becomes its own
    row in the segment's doc block, children before the parent, exactly
    like Lucene's block-join document ordering."""
    doc_id: str
    source: dict
    routing: Optional[str]
    fields: Dict[str, ParsedField]
    children: List[Tuple[str, Dict[str, ParsedField]]] = \
        dc_field(default_factory=list)


DEFAULT_MAPPING_LIMIT = 1000  # index.mapping.total_fields.limit default


class MapperService:
    """Holds the mapping for one index; parses documents and merges mapping updates.

    Reference: index/mapper/MapperService.java:725-file. Mapping dict uses the
    REST shape: {"properties": {"f": {"type": "text", "fields": {...}}}}.
    """

    def __init__(self, mapping: Optional[dict] = None,
                 analysis_registry: Optional[AnalysisRegistry] = None,
                 dynamic: Any = True, total_fields_limit: int = DEFAULT_MAPPING_LIMIT):
        self.analysis = analysis_registry or get_default_registry()
        self.field_types: Dict[str, MappedFieldType] = {}
        # see expand_field_patterns below
        self._multi_children: Dict[str, List[str]] = {}  # parent → direct sub-fields
        # nested object paths (index/mapper/ObjectMapper nested=true): each
        # value under such a path becomes its own segment row (doc block)
        self.nested_paths: set = set()
        # parent-join (modules/parent-join JoinFieldMapper): one join field
        # per index; relations maps parent type -> [child types]
        self.join_field: Optional[str] = None
        self.join_relations: Dict[str, List[str]] = {}
        self.dynamic = dynamic
        self.total_fields_limit = total_fields_limit
        self._source_enabled = True
        # monotonically bumped per merge: keys compiled template skeletons
        # (search/compile.py compile_interned), whose captured field types
        # must not survive a mapping change
        self.version = 0
        if mapping:
            self.merge(mapping)

    # ------------------------------------------------------------- mapping
    def merge(self, mapping: dict):
        self.version += 1
        mapping = mapping.get("mappings", mapping)
        if "dynamic" in mapping:
            self.dynamic = mapping["dynamic"]
        src = mapping.get("_source")
        if isinstance(src, dict) and "enabled" in src:
            self._source_enabled = bool(src["enabled"])
        self._merge_properties("", mapping.get("properties", {}))

    def _merge_properties(self, prefix: str, properties: dict):
        for name, spec in properties.items():
            if not isinstance(spec, dict):
                raise MapperParsingError(f"Expected map for property [{prefix}{name}]")
            full = f"{prefix}{name}"
            sub_properties = spec.get("properties")
            if spec.get("type") == "nested":
                self.nested_paths.add(full)
                self._merge_properties(f"{full}.", sub_properties or {})
                continue
            if spec.get("type") == "join":
                # one join field per index (JoinFieldMapper); the relation
                # name indexes like a keyword, the parent id goes into a
                # hidden <field>#parent keyword column for the host join
                self.join_field = full
                for parent, kids in (spec.get("relations") or {}).items():
                    self.join_relations[parent] = (
                        kids if isinstance(kids, list) else [kids])
                self._put_field(full, {"type": "keyword"})
                self._put_field(f"{full}#parent", {"type": "keyword"})
                continue
            if sub_properties is not None or spec.get("type") == "object":
                self._merge_properties(f"{full}.", sub_properties or {})
                continue
            ftype = spec.get("type")
            if ftype is None:
                raise MapperParsingError(
                    f"No type specified for field [{full}]")
            self._put_field(full, spec)

    def _put_field(self, full_name: str, spec: dict):
        ftype = spec.get("type")
        known = (TEXT_TYPES | KEYWORD_TYPES | NUMERIC_TYPES | DATE_TYPES | VECTOR_TYPES
                 | RANK_VECTOR_TYPES
                 | BOOL_TYPES | IP_TYPES | GEO_TYPES | RANGE_TYPES
                 | {"object", "binary", "percolator"})
        if ftype not in known:
            raise MapperParsingError(
                f"No handler for type [{ftype}] declared on field [{full_name.split('.')[-1]}]")
        if ftype in RANGE_TYPES and not full_name.endswith(("#lo", "#hi")):
            # hidden inclusive-bound columns back every range field
            # (reference RangeFieldMapper encodes ranges in BinaryDocValues;
            # two numeric columns give the same query power on device)
            elem = _RANGE_ELEM[ftype]
            self._put_field(f"{full_name}#lo", {"type": elem, **({"format": spec["format"]} if "format" in spec else {})})
            self._put_field(f"{full_name}#hi", {"type": elem, **({"format": spec["format"]} if "format" in spec else {})})
        existing = self.field_types.get(full_name)
        if existing is not None and existing.type != ftype:
            raise IllegalArgumentError(
                f"mapper [{full_name}] cannot be changed from type [{existing.type}] "
                f"to [{ftype}]")
        if len(self.field_types) >= self.total_fields_limit and existing is None:
            raise IllegalArgumentError(
                f"Limit of total fields [{self.total_fields_limit}] has been exceeded")
        dims = 0
        if ftype in VECTOR_TYPES or ftype in RANK_VECTOR_TYPES:
            dims = int(spec.get("dimension", spec.get("dims", 0)))
            if dims <= 0:
                raise MapperParsingError(
                    f"dimension must be set for vector field [{full_name}]")
        max_tokens = 0
        compression = "none"
        pq_m = 0
        if ftype in RANK_VECTOR_TYPES:
            max_tokens = int(spec.get("max_tokens", DEFAULT_MAX_TOKENS))
            if max_tokens <= 0:
                raise MapperParsingError(
                    f"max_tokens must be a positive integer for "
                    f"rank_vectors field [{full_name}]")
            compression = str(spec.get("compression", "none"))
            if compression not in RANK_VECTORS_COMPRESSION:
                raise MapperParsingError(
                    f"compression must be one of "
                    f"{list(RANK_VECTORS_COMPRESSION)} for rank_vectors "
                    f"field [{full_name}], got [{compression}]")
            if compression == "pq":
                # subspace count: explicit `pq_m` or the widest divisor
                # giving 4-dim subvectors (falling back to scalar
                # subspaces for odd dims)
                pq_m = int(spec.get("pq_m",
                                    dims // 4 if dims % 4 == 0 else dims))
                if pq_m <= 0 or dims % pq_m != 0:
                    raise MapperParsingError(
                        f"pq_m [{pq_m}] must evenly divide dimension "
                        f"[{dims}] for rank_vectors field [{full_name}]")
        analyzer = spec.get("analyzer", "standard")
        if not self.analysis.has(analyzer):
            raise MapperParsingError(
                f"analyzer [{analyzer}] has not been configured in mappings")
        method_spec = spec.get("method", {}) or {}
        space = method_spec.get("space_type", spec.get("space_type", "l2"))
        # HNSW has no TPU-friendly equivalent (pointer-chasing graph walk);
        # map it to IVF, the dense ANN structure (BASELINE.md config 5)
        method_name = method_spec.get("name", "exact")
        if method_name in ("hnsw", "ivf"):
            method_name = "ivf"
        method_params = method_spec.get("parameters", {}) or {}
        if ftype == "geo_point":
            for axis in ("lat", "lon"):
                self.field_types[f"{full_name}.{axis}"] = MappedFieldType(
                    name=f"{full_name}.{axis}", type="double")
        if ftype == "geo_shape":
            # hidden bbox columns back every shape (the device-side coarse
            # filter; exact refinement parses geometries from _source —
            # reference contrast: AbstractShapeGeometryFieldMapper encodes
            # a triangle tree into BKD points)
            for corner in ("minx", "maxx", "miny", "maxy"):
                self.field_types[f"{full_name}#{corner}"] = MappedFieldType(
                    name=f"{full_name}#{corner}", type="double")
        self.field_types[full_name] = MappedFieldType(
            name=full_name, type=ftype,
            analyzer=analyzer,
            search_analyzer=spec.get("search_analyzer"),
            index=bool(spec.get("index", True)),
            doc_values=bool(spec.get("doc_values", True)),
            store=bool(spec.get("store", False)),
            fmt=spec.get("format"),
            scaling_factor=float(spec.get("scaling_factor", 100.0)),
            dims=dims,
            similarity_space=space,
            knn_method=method_name,
            knn_nlist=int(method_params.get("nlist", 128)),
            knn_nprobe=int(method_params.get("nprobes",
                                             method_params.get("nprobe", 0))),
            max_tokens=max_tokens,
            compression=compression,
            pq_m=pq_m,
            ignore_above=spec.get("ignore_above"),
            null_value=spec.get("null_value"),
            boost=float(spec.get("boost", 1.0)),
            meta=spec.get("meta", {}),
        )
        for sub_name, sub_spec in spec.get("fields", {}).items():
            sub_full = f"{full_name}.{sub_name}"
            self._put_field(sub_full, sub_spec)
            children = self._multi_children.setdefault(full_name, [])
            if sub_full not in children:
                children.append(sub_full)

    def mapping_dict(self) -> dict:
        """Render back the REST mapping shape (GET _mapping contract)."""
        properties: dict = {}
        multi_fields = [n for n in self.field_types if "." in n
                        and n.rsplit(".", 1)[0] in self.field_types]
        for name, ft in self.field_types.items():
            if name in multi_fields:
                continue
            spec: dict = {"type": ft.type}
            if ft.is_vector:
                spec["dimension"] = ft.dims
            if ft.is_rank_vectors:
                spec["dimension"] = ft.dims
                spec["max_tokens"] = ft.max_tokens
                if ft.compression != "none":
                    spec["compression"] = ft.compression
                    spec["pq_m"] = ft.pq_m
            if ft.fmt:
                spec["format"] = ft.fmt
            if ft.analyzer != "standard" and ft.is_text:
                spec["analyzer"] = ft.analyzer
            subs = {m.rsplit(".", 1)[1]: {"type": self.field_types[m].type}
                    for m in multi_fields if m.rsplit(".", 1)[0] == name}
            for sub_name, sub_spec in subs.items():
                if self.field_types[f"{name}.{sub_name}"].ignore_above is not None:
                    sub_spec["ignore_above"] = self.field_types[f"{name}.{sub_name}"].ignore_above
            if subs:
                spec["fields"] = subs
            node = properties
            parts = name.split(".")
            for p in parts[:-1]:
                node = node.setdefault(p, {}).setdefault("properties", {})
            node[parts[-1]] = spec
        return {"properties": properties}

    # ------------------------------------------------------------ documents
    def parse_document(self, doc_id: str, source: dict,
                       routing: Optional[str] = None) -> ParsedDocument:
        if not isinstance(source, dict):
            raise MapperParsingError("failed to parse: document must be an object")
        fields: Dict[str, ParsedField] = {}
        children: List[Tuple[str, Dict[str, ParsedField]]] = []
        self._parse_object("", source, fields, children)
        return ParsedDocument(doc_id=doc_id, source=source, routing=routing,
                              fields=fields, children=children)

    def _parse_object(self, prefix: str, obj: dict,
                      out: Dict[str, ParsedField],
                      children: Optional[List] = None):
        for key, value in obj.items():
            full = f"{prefix}{key}"
            ft = self.field_types.get(full)
            if ft is not None and ft.type == "percolator":
                # stored-query field: kept in _source only, matched at
                # percolate time (modules/percolator PercolatorFieldMapper)
                continue
            if ft is not None and ft.is_range:
                self._parse_range(full, ft, value, out)
                continue
            if ft is not None and ft.type == "geo_shape":
                # GeoJSON dicts must NOT fall into the object walk
                self._parse_value(full, value, out)
                continue
            if full == self.join_field and children is not None:
                # join value: "parent_type" or {"name": t, "parent": id}
                if isinstance(value, dict):
                    self._parse_value(full, value.get("name"), out)
                    if value.get("parent") is not None:
                        self._parse_value(f"{full}#parent",
                                          str(value["parent"]), out)
                else:
                    self._parse_value(full, value, out)
                continue
            if full in self.nested_paths and children is not None:
                # nested object(s): each becomes its own doc-block row;
                # sub-fields do NOT join the parent row's fields. `children`
                # is passed through so nested-inside-nested paths also get
                # their own rows (each joins to the root block).
                elems = value if isinstance(value, list) else [value]
                for elem in elems:
                    if elem is None:
                        continue    # explicit null = absent, like the ref
                    if not isinstance(elem, dict):
                        raise MapperParsingError(
                            f"object mapping for [{full}] tried to parse "
                            f"field as object, but found a concrete value")
                    child_fields: Dict[str, ParsedField] = {}
                    self._parse_object(f"{full}.", elem, child_fields,
                                       children)
                    children.append((full, child_fields))
                continue
            if isinstance(value, dict):
                self._parse_object(f"{full}.", value, out, children)
            elif isinstance(value, list) and value and all(
                    isinstance(v, dict) for v in value):
                for v in value:
                    self._parse_object(f"{full}.", v, out, children)
            else:
                self._parse_value(full, value, out)

    def _parse_range(self, name: str, ft: MappedFieldType, value: Any,
                     out: Dict[str, ParsedField]):
        """Range value(s) {gte/gt/lte/lt} -> inclusive bounds in the hidden
        #lo / #hi columns (RangeFieldMapper analog); exclusive bounds shift
        by one step on discrete elements, one ulp on floats."""
        elem_ft = self.field_types[f"{name}#lo"]
        step = _RANGE_STEP.get(elem_ft.type, 0.0)

        def conv(v):
            if elem_ft.is_date:
                return float(parse_date_millis(v, elem_ft.fmt))
            if elem_ft.is_ip:
                return float(ip_to_long(v))
            return elem_ft.parse_numeric(v)

        lo_pf = out.setdefault(f"{name}#lo", ParsedField())
        hi_pf = out.setdefault(f"{name}#hi", ParsedField())
        lo_pf.numeric_values = lo_pf.numeric_values or []
        hi_pf.numeric_values = hi_pf.numeric_values or []
        for elem in (value if isinstance(value, list) else [value]):
            if elem is None:
                continue
            if not isinstance(elem, dict):
                raise MapperParsingError(
                    f"error parsing field [{name}], expected an object "
                    f"with gte/gt/lte/lt bounds")
            lo, hi = -RANGE_UNBOUNDED, RANGE_UNBOUNDED
            if elem.get("gte") is not None:
                lo = conv(elem["gte"])
            if elem.get("gt") is not None:
                v = conv(elem["gt"])
                lo = v + step if step else math.nextafter(v, math.inf)
            if elem.get("lte") is not None:
                hi = conv(elem["lte"])
            if elem.get("lt") is not None:
                v = conv(elem["lt"])
                hi = v - step if step else math.nextafter(v, -math.inf)
            lo_pf.numeric_values.append(lo)
            hi_pf.numeric_values.append(hi)

    def _dynamic_map(self, name: str, value: Any):
        if self.dynamic in (False, "false", "strict"):
            if self.dynamic == "strict":
                raise MapperParsingError(
                    f"mapping set to strict, dynamic introduction of [{name}] "
                    f"within [_doc] is not allowed")
            return  # dynamic:false — ignore unmapped fields
        sample = value[0] if isinstance(value, list) and value else value
        if isinstance(sample, bool):
            self._put_field(name, {"type": "boolean"})
        elif isinstance(sample, int):
            self._put_field(name, {"type": "long"})
        elif isinstance(sample, float):
            self._put_field(name, {"type": "float"})
        elif isinstance(sample, str):
            try:
                parse_date_millis(sample)
                looks_like_date = bool(re.match(r"^\d{4}-\d{2}-\d{2}", sample))
            except MapperParsingError:
                looks_like_date = False
            if looks_like_date:
                self._put_field(name, {"type": "date"})
            else:
                self._put_field(name, {"type": "text",
                                       "fields": {"keyword": {"type": "keyword",
                                                              "ignore_above": 256}}})
        else:
            return

    def _parse_value(self, name: str, value: Any, out: Dict[str, ParsedField],
                     into_multi_fields: bool = True):
        if name not in self.field_types:
            if value is None:
                return
            self._dynamic_map(name, value)
            if name not in self.field_types:
                return
        if into_multi_fields:
            # fan the same raw value into multi-fields (title → title.keyword)
            for sub in self._multi_children.get(name, ()):
                self._parse_value(sub, value, out, into_multi_fields=False)
        ft = self.field_types[name]
        values = value if isinstance(value, list) else [value]
        values = [v for v in values if v is not None]
        if ft.null_value is not None and not values:
            values = [ft.null_value]
        if not values:
            return
        pf = out.setdefault(name, ParsedField())
        if ft.is_text:
            analyzer = self.analysis.get(ft.analyzer)
            terms: List[Tuple[str, int]] = pf.terms or []
            # continue positions past the last emitted one, with the standard
            # 100-position gap between values (Lucene position_increment_gap)
            base = (terms[-1][1] + 1 + 100) if terms else 0
            for v in values:
                toks = analyzer.analyze(str(v))
                terms.extend((t, base + p) for t, p in toks)
                if toks:
                    base += toks[-1][1] + 1 + 100
            pf.terms = terms
            pf.length = len(terms)
        elif ft.is_keyword:
            vals = pf.exact_values or []
            for v in values:
                s = str(v)
                if ft.ignore_above is not None and len(s) > int(ft.ignore_above):
                    continue
                vals.append(s)
            pf.exact_values = vals
        elif ft.is_numeric:
            nums = pf.numeric_values or []
            nums.extend(ft.parse_numeric(v) for v in values)
            pf.numeric_values = nums
        elif ft.is_date:
            nums = pf.numeric_values or []
            nums.extend(float(parse_date_millis(v, ft.fmt)) for v in values)
            pf.numeric_values = nums
        elif ft.is_bool:
            nums = pf.numeric_values or []
            bools = [_parse_boolish(v) for v in values]
            nums.extend(1.0 if b else 0.0 for b in bools)
            pf.numeric_values = nums
            pf.exact_values = (pf.exact_values or []) + [
                "true" if b else "false" for b in bools]
        elif ft.is_ip:
            nums = pf.numeric_values or []
            nums.extend(float(ip_to_long(v)) for v in values)
            pf.numeric_values = nums
            pf.exact_values = (pf.exact_values or []) + [str(v) for v in values]
        elif ft.is_rank_vectors:
            # one [tokens, dims] matrix per doc: an array of per-token
            # vectors (an empty array is a valid zero-token doc)
            if not isinstance(value, list) or not all(
                    isinstance(t, list) for t in values):
                raise MapperParsingError(
                    f"failed to parse rank_vectors field [{name}]: "
                    f"expected an array of token vectors")
            if len(values) > ft.max_tokens:
                raise MapperParsingError(
                    f"rank_vectors field [{name}] has {len(values)} token "
                    f"vectors, more than max_tokens [{ft.max_tokens}]")
            toks: List[List[float]] = []
            for t in values:
                if len(t) != ft.dims or not all(
                        isinstance(v, (int, float)) and
                        not isinstance(v, bool) for v in t):
                    raise MapperParsingError(
                        f"Vector dimension mismatch for field [{name}]: "
                        f"expected {ft.dims}, got {len(t)}")
                toks.append([float(v) for v in t])
            pf.token_vectors = toks
        elif ft.is_vector:
            if isinstance(value, list) and all(isinstance(v, (int, float)) for v in value):
                vec = [float(v) for v in value]
            else:
                raise MapperParsingError(
                    f"failed to parse vector field [{name}]: expected array of numbers")
            if len(vec) != ft.dims:
                raise MapperParsingError(
                    f"Vector dimension mismatch for field [{name}]: expected {ft.dims}, "
                    f"got {len(vec)}")
            pf.vector = vec
        elif ft.type == "geo_point":
            # store as two aligned numeric columns (.lat/.lon) — a sorted
            # value-pair column would scramble which value is which axis;
            # the parent field keeps lat for exists checks
            if isinstance(value, (list, tuple)) and len(value) == 2 \
                    and all(isinstance(v, (int, float)) for v in value):
                points = [list(value)]  # bare GeoJSON [lon, lat] point
            elif isinstance(value, list):
                points = value
            else:
                points = [value]
            nums = pf.numeric_values or []
            lat_pf = out.setdefault(f"{name}.lat", ParsedField())
            lon_pf = out.setdefault(f"{name}.lon", ParsedField())
            lat_pf.numeric_values = lat_pf.numeric_values or []
            lon_pf.numeric_values = lon_pf.numeric_values or []
            for v in points:
                lat, lon = _parse_geo_point(v)
                nums.append(lat)
                lat_pf.numeric_values.append(lat)
                lon_pf.numeric_values.append(lon)
            pf.numeric_values = nums
        elif ft.type == "geo_shape":
            from opensearch_tpu.common.geo import parse_geojson
            try:
                geom = parse_geojson(value)
            except (ValueError, TypeError, KeyError, IndexError) as e:
                raise MapperParsingError(
                    f"failed to parse field [{name}] of type [geo_shape]: "
                    f"{e}")
            minx, miny, maxx, maxy = geom.bbox
            pf.numeric_values = (pf.numeric_values or []) + [minx]
            for corner, v in (("minx", minx), ("maxx", maxx),
                              ("miny", miny), ("maxy", maxy)):
                cpf = out.setdefault(f"{name}#{corner}", ParsedField())
                cpf.numeric_values = (cpf.numeric_values or []) + [v]
        # binary/object: stored in _source only

    def get_field(self, name: str) -> Optional[MappedFieldType]:
        return self.field_types.get(name)

    def expand_field_patterns(self, fields) -> List[str]:
        """Wildcard field specs ("text*", "*_name^2") expand against the
        mapping (QueryParserHelper.resolveMappingFields), skipping hidden
        bound/join columns; boost suffixes carry to every expansion. The
        single shared implementation for the compiler, highlighter, and
        term collector — the hidden-field filter must never diverge."""
        import fnmatch as _fn
        out: List[str] = []
        for fspec in fields:
            fname, caret, fboost = str(fspec).partition("^")
            if "*" not in fname:
                out.append(fspec)
                continue
            for actual in self.field_types:
                if "#" in actual:
                    continue
                if _fn.fnmatchcase(actual, fname):
                    out.append(f"{actual}^{fboost}" if caret else actual)
        return out


def _parse_geo_point(value: Any) -> Tuple[float, float]:
    if isinstance(value, dict):
        return float(value["lat"]), float(value["lon"])
    if isinstance(value, (list, tuple)) and len(value) == 2:
        return float(value[1]), float(value[0])  # GeoJSON order [lon, lat]
    if isinstance(value, str) and "," in value:
        lat, lon = value.split(",", 1)
        return float(lat), float(lon)
    raise MapperParsingError(f"failed to parse geo_point [{value}]")
