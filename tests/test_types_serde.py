"""Schema + codec round-trip tests (role of thrift serializer tests), and
the compiled decoder against the reflective one it replaced."""

from __future__ import annotations

import copy
import dataclasses
import enum
import inspect
import json
import random
import sys
import threading
import typing
from typing import Any, Optional

import pytest

from openr_tpu import serde, types
from openr_tpu.config import OpenrConfig
from openr_tpu.decision.rib_policy import RibPolicy
from openr_tpu.runtime.counters import counters
from openr_tpu.serde import _strip_optional, _type_hints
from tests.conftest import run_async
from tests.test_decision import AREA, DecisionHarness, adj, adj_db_kv


def test_adjacency_db_roundtrip():
    db = types.AdjacencyDatabase(
        this_node_name="node1",
        adjacencies=(
            types.Adjacency("node2", "if_1_2", "if_2_1", metric=10, rtt_us=1200),
            types.Adjacency(
                "node3", "if_1_3", metric=5, adj_only_used_by_other_node=True
            ),
        ),
        is_overloaded=True,
        node_label=101,
        area="area1",
    )
    assert serde.deserialize(serde.serialize(db), types.AdjacencyDatabase) == db


def test_prefix_db_roundtrip():
    db = types.PrefixDatabase(
        this_node_name="node1",
        prefix_entries=(
            types.PrefixEntry(
                prefix="10.1.0.0/16",
                type=types.PrefixType.BGP,
                metrics=types.PrefixMetrics(path_preference=2000),
                forwarding_type=types.PrefixForwardingType.SR_MPLS,
                forwarding_algorithm=types.PrefixForwardingAlgorithm.KSP2_ED_ECMP,
                min_nexthop=2,
                tags=("tag1",),
            ),
        ),
        delete_prefix=False,
    )
    out = serde.deserialize(serde.serialize(db), types.PrefixDatabase)
    assert out == db
    assert out.prefix_entries[0].forwarding_algorithm is (
        types.PrefixForwardingAlgorithm.KSP2_ED_ECMP
    )


def test_kvstore_value_hash_auto():
    v = types.Value(version=3, originator_id="n1", value=b"payload", ttl_ms=5000)
    assert v.hash is not None
    v2 = types.Value(version=3, originator_id="n1", value=b"payload")
    assert v.hash == v2.hash
    v3 = types.Value(version=4, originator_id="n1", value=b"payload")
    assert v.hash != v3.hash


def test_publication_roundtrip():
    pub = types.Publication(
        key_vals={"adj:n1": types.Value(1, "n1", b"x", ttl_ms=100)},
        expired_keys=["prefix:old"],
        node_ids=["n1", "n2"],
        area="0",
    )
    out = serde.deserialize(serde.serialize(pub), types.Publication)
    assert out.key_vals["adj:n1"].value == b"x"
    assert out.node_ids == ["n1", "n2"]


def test_forward_compat_unknown_and_missing_fields():
    import json

    plain = serde.to_plain(types.Adjacency("n2", "if1"))
    plain["brand_new_field"] = 42  # unknown field ignored
    del plain["weight"]  # missing field -> default
    adj = serde.from_plain(plain, types.Adjacency)
    assert adj.other_node_name == "n2"
    assert adj.weight == 1
    json.dumps(plain)


def test_key_naming():
    assert types.adj_key("node-1") == "adj:node-1"
    assert types.parse_adj_key("adj:node-1") == "node-1"
    assert types.parse_adj_key("prefix:x") is None
    k = types.prefix_key("node-1", "area0", "10.0.0.0/24")
    assert types.parse_prefix_key(k) == ("node-1", "area0", "10.0.0.0/24")
    assert types.parse_prefix_key("garbage") is None


def test_spark_packet_roundtrip():
    pkt = types.SparkPacket(
        hello=types.SparkHelloMsg(
            domain_name="d",
            node_name="n1",
            if_name="eth0",
            seq_num=7,
            neighbor_infos={"n2": types.ReflectedNeighborInfo(seq_num=3)},
            solicit_response=True,
        )
    )
    out = serde.deserialize(serde.serialize(pkt), types.SparkPacket)
    assert out.hello.neighbor_infos["n2"].seq_num == 3
    assert out.handshake is None


# -- the compiled decoder against the reflective one ----------------------


def from_plain(value: Any, tp: Any) -> Any:
    """The oracle: serde.from_plain as it stood before the decoder was
    compiled per annotation (PR 32), verbatim. It re-derives the
    annotation's structure for every value."""
    if value is None:
        return None
    tp = _strip_optional(tp)
    if isinstance(tp, str):  # unresolved forward ref; leave as-is
        return value
    origin = typing.get_origin(tp)
    if origin in (list, set, frozenset):
        (elem_tp,) = typing.get_args(tp) or (Any,)
        seq = [from_plain(v, elem_tp) for v in value]
        return origin(seq) if origin is not list else seq
    if origin is tuple:
        args = typing.get_args(tp)
        if len(args) == 2 and args[1] is Ellipsis:
            return tuple(from_plain(v, args[0]) for v in value)
        return tuple(from_plain(v, a) for v, a in zip(value, args))
    if origin is dict:
        kt, vt = typing.get_args(tp) or (Any, Any)
        out = {}
        for k, v in value.items():
            key = int(k) if kt is int else k
            out[key] = from_plain(v, vt)
        return out
    if tp is bytes or (isinstance(value, dict) and "__bytes__" in value):
        if isinstance(value, dict):
            return bytes.fromhex(value["__bytes__"])
        return value
    if isinstance(tp, type) and issubclass(tp, enum.Enum):
        return tp(value)
    if dataclasses.is_dataclass(tp):
        hints = _type_hints(tp)
        kwargs = {}
        for f in dataclasses.fields(tp):
            if f.name in value:
                kwargs[f.name] = from_plain(value[f.name], hints[f.name])
            # missing fields fall back to dataclass defaults (forward compat)
        return tp(**kwargs)
    if tp in (int, float, str, bool):
        return tp(value)
    return value


@dataclasses.dataclass
class Shapes:
    """The annotations no message class of the tree has."""

    by_label: dict[int, str] = dataclasses.field(default_factory=dict)
    pair: tuple[str, int] = ("", 0)
    members: set[str] = dataclasses.field(default_factory=set)
    ports: frozenset[int] = frozenset()
    either: int | None = None
    several: typing.Union[int, str, None] = None
    anything: Any = None
    bare: list = dataclasses.field(default_factory=list)
    raw: bytes = b""
    ratio: float = 0.0


@dataclasses.dataclass(frozen=True)
class Tree:
    """A class that refers to itself."""

    label: str
    children: tuple[Tree, ...] = ()
    parent_of: Optional[Tree] = None


def _dataclasses_under(tp: Any, seen: dict) -> dict:
    tp = _strip_optional(tp)
    if dataclasses.is_dataclass(tp):
        if tp.__name__ not in seen:
            seen[tp.__name__] = tp
            for hint in _type_hints(tp).values():
                _dataclasses_under(hint, seen)
    else:
        for arg in typing.get_args(tp):
            _dataclasses_under(arg, seen)
    return seen


def _message_classes() -> dict:
    out = {}
    for _, cls in inspect.getmembers(types, inspect.isclass):
        if dataclasses.is_dataclass(cls) and cls.__module__ == types.__name__:
            _dataclasses_under(cls, out)
    for root in (OpenrConfig, RibPolicy, Shapes, Tree):
        _dataclasses_under(root, out)
    return out


MESSAGE_CLASSES = _message_classes()


def _sample(tp: Any, rng: random.Random, depth: int = 0) -> Any:
    """A non-default value of annotation `tp`."""
    tp = _strip_optional(tp)
    origin, args = typing.get_origin(tp), typing.get_args(tp)
    if origin in (list, set, frozenset):
        n = rng.randint(1, 3) if depth < 3 else 0
        return origin(_sample(args[0], rng, depth + 1) for _ in range(n))
    if origin is tuple:
        if len(args) == 2 and args[1] is Ellipsis:
            n = rng.randint(1, 3) if depth < 3 else 0
            return tuple(_sample(args[0], rng, depth + 1) for _ in range(n))
        return tuple(_sample(a, rng, depth + 1) for a in args)
    if origin is dict:
        return {
            _sample(args[0], rng, depth + 1): _sample(args[1], rng, depth + 1)
            for _ in range(rng.randint(1, 3) if depth < 3 else 0)
        }
    if tp is bytes:
        return rng.randbytes(rng.randint(1, 6))
    if isinstance(tp, type) and issubclass(tp, enum.Enum):
        return rng.choice(list(tp))
    if dataclasses.is_dataclass(tp):
        return _instance(tp, rng, everything=depth < 3, depth=depth + 1)
    if tp is bool:
        return True
    if tp is float:
        return rng.randint(1, 10**6) / 8
    if tp is str or isinstance(tp, str):
        return f"s{rng.randint(0, 10**6)}"
    return rng.randint(2, 10**6)  # int, Any, a bare container, a union


def _instance(cls: type, rng: random.Random, everything: bool, depth: int = 0):
    """`cls` with every field sampled, or only those with no default."""
    hints = _type_hints(cls)
    return cls(**{
        f.name: _sample(hints[f.name], rng, depth)
        for f in dataclasses.fields(cls)
        if everything or (
            f.default is dataclasses.MISSING
            and f.default_factory is dataclasses.MISSING
        )
    })


def _payloads(cls: type, rng: random.Random) -> dict:
    """What a wire may carry for `cls`: both instances as written, and the
    second with an unknown field, each field in turn missing, null, a
    string, an int and a {"__bytes__": ...} value."""
    out = {}
    for label, everything in (("defaults", False), ("sampled", True)):
        wire = serde.serialize(_instance(cls, rng, everything))
        out[label] = json.loads(wire)
    base = out["sampled"]
    out["unknown_field"] = {**base, "brand_new_field": {"x": [1, 2]}}
    for name in base:
        for label, put in (
            ("null", None),
            ("string", "17"),
            ("word", "seventeen"),
            ("int", 3),
            ("bytes", {"__bytes__": "00ff10"}),
            ("int_keyed", {"7": "x", "07": {"__bytes__": "ab"}}),
            ("list", ["4", 5]),
        ):
            out[f"{name}.{label}"] = {**base, name: put}
        out[f"{name}.missing"] = {k: v for k, v in base.items() if k != name}
    out["not_a_mapping"] = [1, 2]
    out["bytes_for_the_whole"] = {**base, "__bytes__": "beef"}
    return out


def _outcome(decode, plain: Any, tp: Any):
    try:
        return decode(copy.deepcopy(plain), tp)
    except Exception as exc:  # the decoder's own failures are the outcome
        return type(exc)


def _identical(a: Any, b: Any) -> bool:
    """Equal, and of the same types all the way down (1 == 1.0 == True)."""
    if type(a) is not type(b):
        return False
    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        return all(
            _identical(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a)
        )
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(map(_identical, a, b))
    if isinstance(a, dict):
        return len(a) == len(b) and all(
            _identical(ka, kb) and _identical(va, vb)
            for (ka, va), (kb, vb) in zip(a.items(), b.items())
        )
    return a == b


@pytest.mark.parametrize("name", sorted(MESSAGE_CLASSES))
def test_compiled_decoder_equals_the_reflective_one(name):
    cls = MESSAGE_CLASSES[name]
    rng = random.Random(f"serde-{name}")
    decoded = 0
    for label, plain in _payloads(cls, rng).items():
        want = _outcome(from_plain, plain, cls)
        got = _outcome(serde.from_plain, plain, cls)
        assert _identical(want, got), (name, label, want, got)
        decoded += dataclasses.is_dataclass(got) and not isinstance(got, type)
    assert decoded >= 3, name  # both instances and the unknown field, at least
    # the wire round-trips through the new decoder alone, too
    inst = _instance(cls, rng, everything=True)
    assert _identical(serde.deserialize(serde.serialize(inst), cls), inst)


@pytest.mark.parametrize("tp,plain", [
    (Optional[int], "12"),
    (int | None, None),
    (list[types.PrefixType], [1, 3]),
    (list[types.PrefixType], [99]),
    (dict[int, types.Adjacency], {"4": {"other_node_name": "a", "if_name": "b"}}),
    (dict[str, int], {"__bytes__": "00"}),
    (dict, {"__bytes__": "00"}),
    (tuple[int, str], ["1", 2, 3]),
    (tuple[()], [1]),
    (tuple, [1, 2]),
    (set[int], ["1", 1, 2]),
    (frozenset[str], ["a"]),
    (list[Any], [{"__bytes__": "0a"}, {"a": 1}, None]),
    (bytes, "plain"),
    (bytes, {"no_marker": 1}),
    (float, 3),
    (bool, 0),
    (str, 5),
    (int, True),
    (int, "x"),
    (int, {"__bytes__": "0b"}),
    ("AForwardRef", {"__bytes__": "0c"}),
    (Any, {"__bytes__": "0d"}),
    (types.PrefixType, None),
    (types.Adjacency, "a string"),
    (types.PrefixMetrics, []),
    (typing.List[int], ["1"]),
    (typing.Dict[int, typing.Tuple[int, ...]], {"1": ["2", 3]}),
], ids=repr)
def test_compiled_decoder_equals_the_reflective_one_on_bare_annotations(
    tp, plain
):
    want = _outcome(from_plain, plain, tp)
    got = _outcome(serde.from_plain, plain, tp)
    assert _identical(want, got), (tp, plain, want, got)


ADJ_DB = types.AdjacencyDatabase(
    this_node_name="pod001-fsw03",
    adjacencies=(
        types.Adjacency(
            "pod001-rsw00", "if_3_0", "if_0_3", metric=2, adj_label=50001,
            rtt_us=140, timestamp_s=1700000000, weight=3,
        ),
        types.Adjacency(
            "plane3-ssw07", "if_3_s7", is_overloaded=True,
            adj_only_used_by_other_node=True,
        ),
    ),
    is_overloaded=True, node_label=1203, area="fabric",
    node_metric_increment=7,
)
PREFIX_DB = types.PrefixDatabase(
    this_node_name="pod001-rsw00",
    prefix_entries=(
        types.PrefixEntry(
            prefix="fc00:1::/64", type=types.PrefixType.BGP,
            metrics=types.PrefixMetrics(path_preference=2000, distance=3),
            forwarding_type=types.PrefixForwardingType.SR_MPLS,
            forwarding_algorithm=types.PrefixForwardingAlgorithm.KSP2_ED_ECMP,
            min_nexthop=2, weight=5, tags=("edge", "v6"), area_stack=("0",),
        ),
        types.PrefixEntry(prefix="10.1.0.0/16"),
    ),
    area="fabric", delete_prefix=True,
)
# what the parent of PR 32 wrote for the two: the benchmark builds every
# key's value with serialize, so a different byte is a different benchmark
GOLDEN = {
    "adjacency_database": (ADJ_DB, (
        b'{"this_node_name":"pod001-fsw03","adjacencies":[{"other_node_name":'
        b'"pod001-rsw00","if_name":"if_3_0","other_if_name":"if_0_3","metric"'
        b':2,"adj_label":50001,"is_overloaded":false,"rtt_us":140,"timestamp_'
        b's":1700000000,"next_hop_v6":"","next_hop_v4":"","weight":3,"adj_onl'
        b'y_used_by_other_node":false},{"other_node_name":"plane3-ssw07","if_'
        b'name":"if_3_s7","other_if_name":"","metric":1,"adj_label":0,"is_ove'
        b'rloaded":true,"rtt_us":0,"timestamp_s":0,"next_hop_v6":"","next_hop'
        b'_v4":"","weight":1,"adj_only_used_by_other_node":true}],"is_overloa'
        b'ded":true,"node_label":1203,"area":"fabric","node_metric_increment"'
        b':7}'
    )),
    "prefix_database": (PREFIX_DB, (
        b'{"this_node_name":"pod001-rsw00","prefix_entries":[{"prefix":"fc00:'
        b'1::/64","type":3,"metrics":{"path_preference":2000,"source_preferen'
        b'ce":100,"distance":3,"drain_metric":0},"forwarding_type":1,"forward'
        b'ing_algorithm":1,"min_nexthop":2,"prepend_label":null,"weight":5,"t'
        b'ags":["edge","v6"],"area_stack":["0"]},{"prefix":"10.1.0.0/16","typ'
        b'e":1,"metrics":{"path_preference":1000,"source_preference":100,"dis'
        b'tance":0,"drain_metric":0},"forwarding_type":0,"forwarding_algorith'
        b'm":0,"min_nexthop":null,"prepend_label":null,"weight":null,"tags":['
        b'],"area_stack":[]}],"area":"fabric","delete_prefix":true}'
    )),
}


@pytest.mark.parametrize("kind", sorted(GOLDEN))
def test_the_wire_is_byte_identical(kind):
    obj, wire = GOLDEN[kind]
    assert serde.serialize(obj) == wire
    assert _identical(serde.deserialize(wire, type(obj)), obj)


def test_a_class_that_refers_to_itself_decodes():
    tree = Tree("root", (Tree("a", (Tree("a1"),)), Tree("b")), Tree("up"))
    assert serde.deserialize(serde.serialize(tree), Tree) == tree


def test_a_build_that_fails_leaves_nothing_half_built():
    @dataclasses.dataclass
    class Unresolvable:
        inner: types.PrefixMetrics
        x: "DefinedNowhere" = None  # noqa: F821

    for _ in range(2):
        with pytest.raises(NameError):
            serde.from_plain({"x": 1}, Unresolvable)
        assert not serde._STAGED and Unresolvable not in serde._DECODERS
    assert serde.from_plain({}, types.PrefixMetrics) == types.PrefixMetrics()


def test_concurrent_first_use_builds_each_decoder_once():
    """More threads than cores meet twenty classes no decoder exists for:
    every thread decodes the same objects through the same decoder, and
    nothing is left staged."""
    classes = [
        dataclasses.make_dataclass(f"Fresh{i}", [
            ("n", int, 0),
            ("inner", Optional[types.PrefixMetrics], None),
            ("adjs", tuple[types.Adjacency, ...], ()),
        ])
        for i in range(20)
    ]
    plain = {
        "n": "3", "inner": {"distance": 2},
        "adjs": [{"other_node_name": "a", "if_name": "b"}],
    }
    workers = 16
    start = threading.Barrier(workers)
    seen = [None] * workers

    def work(slot: int) -> None:
        start.wait(timeout=30)
        seen[slot] = [
            (serde.from_plain(plain, cls), serde.decoder_for(cls))
            for cls in classes
        ]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(target=work, args=(i,)) for i in range(workers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for cls, (obj, dec) in zip(classes, seen[0]):
        assert obj == cls(
            3, types.PrefixMetrics(distance=2), (types.Adjacency("a", "b"),)
        )
    for got in seen[1:]:
        assert [o for o, _ in got] == [o for o, _ in seen[0]]
        assert all(d is d0 for (_, d), (_, d0) in zip(got, seen[0]))
    assert not serde._STAGED
    assert counters.get_counter("serde.decoders_built") == len(serde._DECODERS)


def test_constructors_are_called():
    plain = serde.to_plain(types.Value(3, "n1", b"payload"))
    del plain["hash"]
    assert serde.from_plain(plain, types.Value).hash == (
        types.Value(3, "n1", b"payload").hash
    )


def test_decoder_is_built_once():
    """A decoder rebuilt per call would move the gauge."""

    @dataclasses.dataclass
    class Fresh:
        n: int = 0

    before = counters.get_counter("serde.decoders_built") or 0
    assert serde.from_plain({"n": "4"}, Fresh) == Fresh(4)
    serde.deserialize(GOLDEN["adjacency_database"][1], types.AdjacencyDatabase)
    built = counters.get_counter("serde.decoders_built")
    assert built > before
    assert serde.decoder_for(Fresh) is serde.decoder_for(Fresh)
    for i in range(1000):
        db = dataclasses.replace(ADJ_DB, this_node_name=f"node-{i}")
        assert serde.deserialize(
            serde.serialize(db), types.AdjacencyDatabase
        ) == db
    assert counters.get_counter("serde.decoders_built") == built


MALFORMED_ADJ_DBS = {
    "not_json": b"\xff{",
    "adjacency_without_its_neighbour": (
        b'{"this_node_name":"2","adjacencies":[{"if_name":"if-2-1"}]}'
    ),
    "adjacencies_not_a_list": b'{"this_node_name":"2","adjacencies":7}',
    "metric_not_a_number": (
        b'{"this_node_name":"2","adjacencies":[{"other_node_name":"1",'
        b'"if_name":"if-2-1","metric":"high"}]}'
    ),
    "no_node_name": b'{"adjacencies":[]}',
}


@pytest.mark.parametrize("kind", sorted(MALFORMED_ADJ_DBS))
@run_async
async def test_malformed_adjacency_database_raises_and_is_counted(kind):
    raw = MALFORMED_ADJ_DBS[kind]
    with pytest.raises((ValueError, TypeError)):
        serde.deserialize(raw, types.AdjacencyDatabase)
    async with DecisionHarness() as h:
        key, val = adj_db_kv("2", [adj("2", "1", metric=5)])
        h.decision.process_publication(
            types.Publication(key_vals={key: val}, area=AREA)
        )
        before = counters.get_counter("decision.lsdb_parse_errors") or 0
        bad = dataclasses.replace(val, version=2, value=raw)
        h.decision.process_publication(
            types.Publication(key_vals={key: bad}, area=AREA)
        )
        assert counters.get_counter("decision.lsdb_parse_errors") == before + 1
        # the database it had stands
        dbs = h.decision.area_link_states[AREA].get_adjacency_databases()
        assert dbs["2"].adjacencies[0].metric == 5
