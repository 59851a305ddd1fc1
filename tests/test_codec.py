"""`utils/codec.py` by itself (ISSUE 35): the codec resolves a class
once and keeps one generated encoder and decoder for it; the WIRE FORM
and the OBJECTS are the contract, since the raft log, FSM snapshots,
followers, the HTTP API and the client's state DB all read them.  The
oracle is the reflective codec as commit c8d58ca had it, kept below as
the plain reference: the same values give the same plain data, key for
key and in the same key order, and the same plain data gives equal
objects; containers alias the payload no wider than they did; the two
counters say what was compiled and what fell back."""
from __future__ import annotations

import collections
import copy
import dataclasses
import enum
import json
import sys
import threading
import typing
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple, Union

import pytest

from nomad_tpu import mock, structs
from nomad_tpu.server import server
from nomad_tpu.state.store import JobSummary
from nomad_tpu.structs import (AllocatedDeviceResource, Allocation,
                               NetworkResource, PlanResult, Port)
from nomad_tpu.utils import codec
from nomad_tpu.utils.codec import from_wire, to_wire
from nomad_tpu.utils.metrics import global_metrics


# ------------------------------------------- the plain reference (c8d58ca)
_ref_hints_cache: Dict[type, Dict[str, Any]] = {}


def ref_to_wire(obj: Any) -> Any:
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out = {}
        for f in dataclasses.fields(obj):
            out[f.name] = ref_to_wire(getattr(obj, f.name))
        return out
    if isinstance(obj, dict):
        return {k: ref_to_wire(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [ref_to_wire(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if isinstance(obj, bytes):
        import base64
        return {"__b64__": base64.b64encode(obj).decode("ascii")}
    if isinstance(obj, set):
        return sorted(ref_to_wire(v) for v in obj)
    if hasattr(obj, "__dict__"):
        return {k: ref_to_wire(v) for k, v in vars(obj).items()
                if not k.startswith("_")}
    raise TypeError(f"cannot encode {type(obj).__name__}")


def _ref_hints(cls: type) -> Dict[str, Any]:
    if cls not in _ref_hints_cache:
        _ref_hints_cache[cls] = typing.get_type_hints(cls)
    return _ref_hints_cache[cls]


def ref_from_wire(cls: Any, data: Any) -> Any:
    if data is None:
        return None
    origin = typing.get_origin(cls)
    if origin is Union:
        args = [a for a in typing.get_args(cls) if a is not type(None)]
        if len(args) == 1:
            return ref_from_wire(args[0], data)
        return data
    if origin in (list, tuple):
        (elem,) = typing.get_args(cls)[:1] or (Any,)
        return [ref_from_wire(elem, v) for v in data]
    if origin is dict:
        args = typing.get_args(cls)
        val_t = args[1] if len(args) == 2 else Any
        return {k: ref_from_wire(val_t, v) for k, v in data.items()}
    if origin is set:
        (elem,) = typing.get_args(cls)[:1] or (Any,)
        return {ref_from_wire(elem, v) for v in data}
    if dataclasses.is_dataclass(cls):
        kwargs = {}
        hints = _ref_hints(cls)
        field_names = {f.name for f in dataclasses.fields(cls)}
        for key, value in data.items():
            if key in field_names:
                kwargs[key] = ref_from_wire(hints.get(key, Any), value)
        return cls(**kwargs)
    if cls is bytes:
        import base64
        if isinstance(data, dict) and "__b64__" in data:
            return base64.b64decode(data["__b64__"])
        return data.encode() if isinstance(data, str) else data
    if cls in (Any, object) or cls is None:
        return data
    if cls in (int, float, str, bool):
        return cls(data) if data is not None else data
    return data


# ------------------------------------------------------------ helpers
def _outcome(fn, *args):
    """What a call gives: its value, or the type of what it raises."""
    try:
        return "value", fn(*args)
    except Exception as e:          # noqa: BLE001 - the type is the result
        return "raises", type(e)


def _same_wire(obj):
    """`to_wire` against the reference: equal, the same key order at
    every depth (`json.dumps` keeps a dict's order), a plain dict."""
    got, want = to_wire(obj), ref_to_wire(obj)
    assert got == want
    assert json.dumps(got) == json.dumps(want)
    return got


def _same_objects(cls, wire):
    """`from_wire` against the reference, on the payload as it is and
    as the durable log and the HTTP API carry it (through JSON)."""
    for payload in (wire, json.loads(json.dumps(wire))):
        got, want = from_wire(cls, payload), ref_from_wire(cls, payload)
        assert got == want and type(got) is type(want)
    return got


def _fill(hint, seen=()):
    """A value for `hint` with nothing left at its default: every field
    of every dataclass filled, every container holding two entries."""
    origin = typing.get_origin(hint)
    if origin is Union:
        args = [a for a in typing.get_args(hint) if a is not type(None)]
        return _fill(args[0], seen)
    if origin in (list, tuple):
        (elem,) = typing.get_args(hint)[:1] or (Any,)
        return [_fill(elem, seen), _fill(elem, seen)]
    if origin is dict:
        args = typing.get_args(hint)
        val = args[1] if len(args) == 2 else Any
        return {"k1": _fill(val, seen), "k2": _fill(val, seen)}
    if origin is set:
        return {_fill(typing.get_args(hint)[0], seen)}
    if dataclasses.is_dataclass(hint):
        if hint in seen:
            return None
        hints = typing.get_type_hints(hint)
        return hint(**{f.name: _fill(hints[f.name], seen + (hint,))
                       for f in dataclasses.fields(hint)})
    if hint is bytes:
        return b"\x00\xffpayload"
    if hint in (int, float, str, bool):
        return {int: 7, float: 2.5, str: "s", bool: True}[hint]
    return {"any": [1, None, {"x": 2.5}], "n": None}


STRUCTS = sorted(n for n in dir(structs)
                 if isinstance(getattr(structs, n), type)
                 and dataclasses.is_dataclass(getattr(structs, n)))


def _networked_alloc():
    """c5's shape: a task network with a static and a dynamic port, one
    more dynamic port on the group's shared network."""
    a = mock.alloc()
    task = next(iter(a.allocated_resources.tasks.values()))
    task.networks = [NetworkResource(
        device="eth0", ip="10.0.3.7", mbits=100,
        reserved_ports=[Port("http", 8080, 8080)],
        dynamic_ports=[Port("admin", 23417), Port("metrics", 31002)])]
    a.allocated_resources.shared.networks = [NetworkResource(
        mode="bridge", ip="10.0.3.7", mbits=10,
        dynamic_ports=[Port("mesh", 20111, 9000, "default")])]
    return a


def _device_alloc():
    """c4's shape: a task holding one instance of a device."""
    a = mock.alloc()
    task = next(iter(a.allocated_resources.tasks.values()))
    task.networks = []
    task.devices = [AllocatedDeviceResource(
        "google", "tpu", "v4", [f"{a.node_id[:8]}-tpu-3"])]
    return a


def _plan_of_64():
    from test_raft_plan_entry import _c3_job, _placements, _plan_of
    job = _c3_job()
    nodes = [mock.node() for _ in range(8)]
    plan, result = _plan_of(job, _placements(job, nodes, 16))
    assert sum(len(v) for v in result.node_allocation.values()) == 64
    return plan, result


FIXTURES = {
    "job": mock.job, "system_job": mock.system_job,
    "batch_job": mock.batch_job, "node": mock.node,
    "gpu_node": mock.gpu_node, "alloc": mock.alloc, "eval": mock.eval_,
    "plan_64": lambda: _plan_of_64()[0],
    "plan_result_64": lambda: _plan_of_64()[1],
    "alloc_with_ports": _networked_alloc,
    "alloc_with_device": _device_alloc,
}


# -------------------------------------------------- the structs, both ways
@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_mock_objects_cross_as_the_reference_has_them(name):
    obj = FIXTURES[name]()
    wire = _same_wire(obj)
    back = _same_objects(type(obj), wire)
    assert back == obj
    assert to_wire(back) == wire


@pytest.mark.parametrize("name", STRUCTS)
def test_every_struct_filled_crosses_as_the_reference_has_it(name):
    cls = getattr(structs, name)
    obj = _fill(cls)
    wire = _same_wire(obj)
    back = _same_objects(cls, wire)
    assert _same_wire(back) == wire


@pytest.mark.parametrize("name", STRUCTS)
def test_missing_keys_take_defaults_and_unknown_keys_are_ignored(name):
    cls = getattr(structs, name)
    wire = ref_to_wire(_fill(cls))
    sparse = {k: v for i, (k, v) in enumerate(wire.items()) if i % 2}
    sparse["a_key_of_some_later_version"] = {"x": [1]}
    nulls = dict.fromkeys(wire)
    for payload in ({}, sparse, nulls):
        got = _outcome(from_wire, cls, payload)
        want = _outcome(ref_from_wire, cls, payload)
        if want[0] == "raises":
            # a class with a required field raises without it, both ways
            assert got == want
            continue
        assert type(got[1]) is cls
        # a default that is made anew each time (an id) differs between
        # two decodes of the reference too: every other field is equal
        again = ref_to_wire(ref_from_wire(cls, payload))
        want = ref_to_wire(want[1])
        fresh = {k for k in want if want[k] != again[k]}
        assert not fresh & set(payload)
        got = ref_to_wire(got[1])
        assert list(got) == list(want)
        assert {k: v for k, v in got.items() if k not in fresh} \
            == {k: v for k, v in want.items() if k not in fresh}


def test_a_plan_entry_is_the_entry_the_reference_writes(monkeypatch):
    plan, result = _plan_of_64()
    got = server._plan_entry(plan, result)
    monkeypatch.setattr(server, "to_wire", ref_to_wire)
    want = server._plan_entry(plan, result)
    assert json.dumps(got) == json.dumps(want)
    assert _same_objects(PlanResult, got["result"]) is not None
    assert _same_objects(structs.Job, got["job"]) == plan.job


# ------------------------------------------------------ decoding, by hint
@dataclass
class _Leaf:
    n: int = 0
    tags: List[str] = field(default_factory=list)


@dataclass
class _Tree:
    """Refers to itself, and to a class defined further down."""
    name: str = ""
    leaf: Optional[_Leaf] = None
    children: List[_Tree] = field(default_factory=list)
    later: Optional[_Later] = None
    by_name: Dict[str, _Leaf] = field(default_factory=dict)


@dataclass
class _Later:
    weight: float = 1.0
    tree: Optional[_Tree] = None


@dataclass
class _Required:
    id: str
    count: int = 3


_DATA = {"a": [1, None, {"b": 2.0}], "n": None}
_B64 = {"__b64__": "AP9wYXlsb2Fk"}

DECODE_CASES = {
    # None stays None at any depth
    "none_top": (Allocation, None),
    "none_in_list": (List[Optional[int]], [1, None, 3.0]),
    "none_in_optional_list": (Optional[List[int]], None),
    "none_in_dict_of_lists": (Dict[str, List[str]],
                              {"a": None, "b": ["x", None, 7]}),
    "none_in_set": (Set[int], [3, None, 1, 3]),
    "none_for_a_dataclass_field": (_Tree, {"name": None, "leaf": None,
                                           "children": None}),
    "none_inside_nested_dataclasses": (_Tree, {
        "children": [None, {"leaf": {"n": None, "tags": [None, "t"]}}],
        "by_name": {"x": None, "y": {"n": 2.0}}}),
    # Optional unwraps, a wider Union hands the data back
    "optional_coerces": (Optional[int], 3.0),
    "optional_dataclass": (Optional[_Leaf], {"n": "4", "tags": [1]}),
    "wide_union_int": (Union[int, str], 3.0),
    "wide_union_dict": (Union[int, str, None], _DATA),
    "union_of_one": (Union[int], 4.0),
    # a primitive hint coerces
    "int_from_float": (int, 3.0), "int_from_str": (int, "12"),
    "int_from_bool": (int, True), "float_from_int": (float, 1),
    "float_from_str": (float, "2.5"), "str_from_int": (str, 7),
    "str_from_float": (str, 1.5), "bool_from_int": (bool, 0),
    "bool_from_str": (bool, "false"), "int_refuses": (int, "x"),
    "int_refuses_a_dict": (int, {}),
    # bare and Any hints hand the data back as it is
    "any": (Any, _DATA), "object": (object, _DATA), "none_hint": (None, _DATA),
    "bare_dict": (dict, _DATA), "bare_list": (list, [1, [2]]),
    "bare_set": (set, [1, 2]), "bare_tuple": (tuple, [1, 2]),
    "a_plain_class": (JobSummary, {"job_id": "j"}),
    "a_typevar": (typing.TypeVar("T"), _DATA),
    "a_literal": (typing.Literal["a", "b"], "a"),
    "a_sequence": (typing.Sequence[int], [1.0, 2.0]),
    "a_frozenset": (typing.FrozenSet[int], [1, 2]),
    # typed containers
    "list_of_int": (List[int], [1.0, 2, "3"]),
    "list_of_any": (List[Any], [1, _DATA]),
    "list_unsubscripted": (List, [1, _DATA]),
    "list_of_dicts": (List[dict], [{"a": 1}, {}]),
    "list_of_lists": (List[List[float]], [[1, 2], [], None]),
    "list_builtin": (list[int], [1.0, 2.0]),
    "tuple_of_int": (Tuple[int, ...], [1.0, 2.0]),
    "tuple_pair": (Tuple[int, str], [1.0, 2.0]),
    "tuple_unsubscripted": (Tuple, [1.0, "x"]),
    "dict_of_int": (Dict[str, int], {"a": 1.0, "b": None}),
    "dict_of_any": (Dict[str, Any], {"a": _DATA, "b": 1}),
    "dict_unsubscripted": (Dict, {"a": _DATA}),
    "dict_builtin": (dict[str, float], {"a": 1}),
    "dict_of_dataclasses": (Dict[str, _Leaf], {"a": {"n": 1.0}, "b": None}),
    "dict_keys_stay": (Dict[int, int], {"1": 2.0}),
    "set_of_str": (Set[str], ["b", "a", "b", 3]),
    "list_given_a_dict": (List[str], {"a": 1, "b": 2}),
    "dict_given_a_list": (Dict[str, int], [1, 2]),
    "dataclass_given_a_list": (_Leaf, [1, 2]),
    # bytes, both forms
    "bytes_b64": (bytes, _B64), "bytes_str": (bytes, "text"),
    "bytes_raw": (bytes, b"raw"), "bytes_other_dict": (bytes, {"x": 1}),
    "optional_bytes": (Optional[bytes], _B64),
    # dataclasses: unknown keys, missing keys, classes that refer to
    # each other and to themselves
    "unknown_keys": (_Leaf, {"n": 1, "zzz": 2, "tags": ["a"]}),
    "missing_keys": (_Leaf, {}),
    "required_given": (_Required, {"id": 7}),
    "required_missing": (_Required, {"count": 2}),
    "self_reference": (_Tree, {"name": "r", "children": [
        {"name": "c", "children": [{"name": "g"}]}, {"name": "d"}]}),
    "mutual_reference": (_Tree, {"later": {"weight": 2, "tree": {
        "name": "inner", "later": {"weight": "3.5"}}}}),
    "subclass_of_a_dataclass": (type("_Sub", (_Leaf,), {}),
                                {"n": 1.0, "tags": [2]}),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_from_wire_decodes_as_the_reference_does(case):
    hint, data = DECODE_CASES[case]
    kept = copy.deepcopy(data)
    got = _outcome(from_wire, hint, data)
    want = _outcome(ref_from_wire, hint, data)
    assert got == want
    assert type(got[1]) is type(want[1])
    assert repr(got[1]) == repr(want[1]), \
        "equal down to the types (1 is not 1.0 on the wire)"
    assert data == kept, "decoding leaves the payload as it was"


@pytest.mark.parametrize("hint", [
    Any, object, None, dict, list, Union[int, str], JobSummary,
    Optional[dict]], ids=str)
def test_an_untyped_hint_hands_back_the_very_object(hint):
    assert from_wire(hint, _DATA) is _DATA
    assert ref_from_wire(hint, _DATA) is _DATA


@dataclass
class _Broken:
    x: NoSuchName = None                    # noqa: F821 - never resolves


@dataclass
class _Holder:
    n: int = 0
    broken: Optional[_Broken] = None
    many: List[_Broken] = field(default_factory=list)


def test_hints_that_do_not_resolve_fail_the_class_that_has_them_only():
    """Hints are resolved at a class's first use: a holder decodes for
    as long as no data of the broken class arrives, as it always has."""
    for payload in ({"n": 1.0}, {"n": 2, "broken": None, "many": []},
                    {"many": [None]}):
        assert from_wire(_Holder, payload) == ref_from_wire(_Holder, payload)
    for payload in ({"broken": {}}, {"many": [{"x": 1}]}):
        assert _outcome(from_wire, _Holder, payload) \
            == _outcome(ref_from_wire, _Holder, payload) \
            == ("raises", NameError)
    assert _outcome(from_wire, _Broken, {}) == ("raises", NameError)
    # and the holder still decodes afterwards
    assert from_wire(_Holder, {"n": "3"}) == _Holder(n=3)
    assert to_wire(_Holder(broken=_Broken(x=[1]))) \
        == ref_to_wire(_Holder(broken=_Broken(x=[1])))


# ------------------------------------------- encoding, by the runtime type
class _Str(str):
    pass


class _Int(int):
    pass


class _Color(enum.IntEnum):
    RED = 1


class _Level(str, enum.Enum):
    HIGH = "high"


class _Bytes(bytes):
    pass


class _MyDict(dict):
    pass


class _MyList(list):
    pass


class _Plain:
    def __init__(self):
        self.shown = {"a": (1, 2)}
        self._hidden = "no"
        self.leaf = _Leaf(1, ["t"])


class _Slotted:
    __slots__ = ("a",)


@dataclass
class _SubLeaf(_Leaf):
    extra: Dict[str, Any] = field(default_factory=dict)


class _UndecoratedSub(_Leaf):
    pass


_Pair = collections.namedtuple("_Pair", "a b")

ENCODE_CASES = {
    "none": None, "true": True, "int": 3, "float": 2.5, "str": "s",
    "nan_stays": float("inf"),
    "str_subclass": _Str("sub"), "int_subclass": _Int(4),
    "int_enum": _Color.RED, "str_enum": _Level.HIGH,
    "bytes": b"\x00\xffpayload", "bytes_empty": b"",
    "bytes_subclass": _Bytes(b"sub"), "bytearray": bytearray(b"no"),
    "set_of_int": {3, 1, 2}, "set_of_str": {"b", "a"}, "set_empty": set(),
    "set_mixed": {1, "a"}, "set_of_tuples": {(2, 1), (1, 2)},
    "frozenset": frozenset({1}),
    "tuple": (1, "a", None), "tuple_nested": {"k": ((1, 2), [3, (4,)])},
    "namedtuple": _Pair(1, {"x": (2,)}),
    "list_subclass": _MyList([1, (2,)]),
    "dict": {"b": 1, "a": {"z": 1, "y": [None]}},
    "dict_subclass": _MyDict(b=1, a=[_Leaf(2)]),
    "ordered_dict": collections.OrderedDict([("z", 1), ("a", 2)]),
    "default_dict": collections.defaultdict(list, {"k": [1]}),
    "dict_with_int_keys": {1: "a", 2: {"b": (1,)}},
    "plain_class": _Plain(), "job_summary": JobSummary("default", "j"),
    "slotted_class": _Slotted(), "an_object": object(),
    "complex": 1j, "a_function": len, "a_lambda": lambda: 0,
    "a_class": _Leaf, "a_dataclass_type_in_a_list": [_Leaf],
    "dataclass": _Leaf(1, ["a"]),
    "dataclass_subclass": _SubLeaf(2, ["b"], {"any": {1, 2}}),
    "dataclass_undecorated_subclass": _UndecoratedSub(3, ["c"]),
    "dataclass_holding_anything": _Tree(
        "t", _Leaf(1), [_Tree("c")], _Later(2.0),
        {"x": _SubLeaf(extra={"b": b"raw", "t": (1,)})}),
    "field_holding_another_type_than_declared": _Leaf("not an int", {
        "not": "a list"}),
    "any_field_holding_a_dataclass": _SubLeaf(extra={"leaf": _Leaf(5)}),
    "deep": [[[{"a": [({"b": _Leaf(1)},)]}]]],
    "unencodable_at_depth": {"a": [_Leaf(1, [object()])]},
}


@pytest.mark.parametrize("case", sorted(ENCODE_CASES))
def test_to_wire_encodes_as_the_reference_does(case):
    value = ENCODE_CASES[case]
    got, want = _outcome(to_wire, value), _outcome(ref_to_wire, value)
    assert got == want
    if got[0] == "value":
        assert type(got[1]) is type(want[1])
        assert json.dumps(got[1]) == json.dumps(want[1])


# --------------------------------------------------------------- aliasing
def _containers(obj, out):
    """Every list, dict and set reachable from `obj`, itself included."""
    if dataclasses.is_dataclass(obj):
        for f in dataclasses.fields(obj):
            _containers(getattr(obj, f.name), out)
    elif isinstance(obj, (list, dict, set)):
        out.append(obj)
        for v in (obj.values() if isinstance(obj, dict) else obj):
            _containers(v, out)
    return out


def _touch(container):
    if isinstance(container, list):
        container.append("touched")
    elif isinstance(container, dict):
        container["touched"] = "touched"
    else:
        container.add("touched")


def _typed_containers(obj, hint, out):
    """The containers of a decoded `obj` that its hints give a type:
    those are rebuilt at every decode.  A bare `dict` / `list` / `Any`
    hint hands the payload's own object back, today as before."""
    if obj is None:
        return out
    origin = typing.get_origin(hint)
    if origin is Union:
        args = [a for a in typing.get_args(hint) if a is not type(None)]
        if len(args) == 1:
            _typed_containers(obj, args[0], out)
    elif origin in (list, tuple, set, dict):
        out.append(obj)
        args = typing.get_args(hint)
        item = (args[1] if len(args) == 2 else Any) if origin is dict \
            else (args[0] if args else Any)
        for v in (obj.values() if origin is dict else obj):
            _typed_containers(v, item, out)
    elif dataclasses.is_dataclass(hint):
        hints = typing.get_type_hints(hint)
        for f in dataclasses.fields(hint):
            _typed_containers(getattr(obj, f.name), hints[f.name], out)
    return out


@pytest.mark.parametrize("name", ["Allocation", "Job", "Node",
                                  "PlanResult", "Evaluation"])
def test_decoding_rebuilds_every_typed_container(name):
    """The in-memory raft log keeps every payload and the store mutates
    what it decoded: no typed container of the object is the payload's."""
    cls = getattr(structs, name)
    payload = ref_to_wire(_fill(cls))
    kept = copy.deepcopy(payload)
    obj = from_wire(cls, payload)
    typed = _typed_containers(obj, cls, [])
    assert len(typed) >= 3
    theirs = {id(c) for c in _containers(payload, [])}
    assert not [c for c in typed if id(c) in theirs]
    for c in typed:
        _touch(c)
    assert payload == kept
    # and no wider than the reference aliases: the same count of shared
    # containers (the bare and Any-typed ones)
    ref_obj = ref_from_wire(cls, kept)
    ref_theirs = {id(c) for c in _containers(kept, [])}
    assert sum(id(c) in theirs for c in _containers(obj, [])) \
        == sum(id(c) in ref_theirs for c in _containers(ref_obj, []))


@pytest.mark.parametrize("hint, data", [
    (List[str], ["a", "b"]), (List[Any], [1, 2]), (List, [1]),
    (Dict[str, int], {"a": 1}), (Dict[str, Any], {"a": 1}), (Dict, {"a": 1}),
    (Set[int], [1, 2]), (Tuple[int, ...], [1, 2]), (Optional[List[int]], [1]),
], ids=str)
def test_a_typed_container_of_primitives_is_still_a_new_one(hint, data):
    got = from_wire(hint, data)
    assert got is not data and got == ref_from_wire(hint, data)


@pytest.mark.parametrize("name", ["Allocation", "Job", "Node",
                                  "PlanResult", "Evaluation"])
def test_to_wire_returns_no_container_the_live_object_owns(name):
    obj = _fill(getattr(structs, name))
    wire = to_wire(obj)
    kept = copy.deepcopy(wire)
    mine = _containers(obj, [])
    assert len(mine) > 3
    theirs = {id(c) for c in _containers(wire, [])}
    assert not [c for c in mine if id(c) in theirs]
    for c in mine:
        _touch(c)
    assert wire == kept


# ---------------------------------------------------------------- threads
def _fresh_classes(tag):
    """Two dataclasses nobody has encoded or decoded yet."""
    inner = dataclasses.make_dataclass(
        f"Inner{tag}", [("n", int, 0), ("tags", List[str], field(
            default_factory=list))])
    outer = dataclasses.make_dataclass(
        f"Outer{tag}", [("name", str, ""),
                        ("inner", Optional[inner], None),
                        ("many", Dict[str, inner], field(
                            default_factory=dict))])
    return inner, outer


def test_two_threads_meeting_a_class_at_once_both_get_it_right():
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for round_ in range(20):
            inner, outer = _fresh_classes(f"T{round_}")
            obj = outer("o", inner(1, ["a"]), {"k": inner(2, [])})
            wire = ref_to_wire(obj)
            n = 4
            gate = threading.Barrier(n)
            results, errors = [], []

            def work():
                try:
                    gate.wait()
                    results.append((to_wire(obj), from_wire(outer, wire)))
                except Exception as e:      # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=work) for _ in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors
            assert results == [(wire, obj)] * n
    finally:
        sys.setswitchinterval(old)


# --------------------------------------------------------------- counters
def _counter(key):
    return global_metrics.dump()["counters"].get(key, 0)


def test_classes_compiled_moves_once_a_function_and_not_again():
    inner, outer = _fresh_classes("Counted")
    obj = outer("o", inner(1, ["a"]), {})
    start = _counter("codec.classes_compiled")
    wire = to_wire(obj)
    assert _counter("codec.classes_compiled") == start + 2, \
        "one encoder each for the two classes"
    assert from_wire(outer, wire) == obj
    assert _counter("codec.classes_compiled") == start + 4, \
        "and one decoder each"
    for _ in range(3):
        assert from_wire(outer, to_wire(obj)) == obj
        to_wire(inner(2, []))
        from_wire(inner, {"n": 1})
    assert _counter("codec.classes_compiled") == start + 4
    assert "codec.fallback" in global_metrics.dump()["counters"], \
        "a window without a fallback reads 0, not nothing"


@pytest.mark.parametrize("value, falls", [
    ({1, 2}, 1), (JobSummary("default", "j"), 1), (_Bytes(b"x"), 1),
    ([{1}, {2}], 2), (b"plain bytes have an encoder", 0),
    ({"a": [1, (2, 3)], "b": _Leaf(1, ["t"])}, 0), (_Str("s"), 0),
    (_MyDict(a=1), 0)], ids=lambda v: type(v).__name__)
def test_fallback_counts_values_sent_down_the_generic_chain(value, falls):
    to_wire(value)                  # whatever is compiled, is compiled
    start = _counter("codec.fallback")
    assert to_wire(value) == ref_to_wire(value)
    assert _counter("codec.fallback") == start + falls


def test_a_plan_entry_falls_back_nowhere_and_compiles_nothing_twice():
    plan, result = _plan_of_64()
    entry = server._plan_entry(plan, result)
    from_wire(PlanResult, entry["result"])
    from_wire(structs.Job, entry["job"])
    start = {k: _counter(k)
             for k in ("codec.fallback", "codec.classes_compiled")}
    entry = server._plan_entry(plan, result)
    from_wire(PlanResult, json.loads(json.dumps(entry["result"])))
    from_wire(structs.Job, json.loads(json.dumps(entry["job"])))
    assert {k: _counter(k) for k in start} == start


def test_the_tables_hold_functions_and_never_a_decoded_value():
    """Nothing is cached ACROSS entries: two decodes of one payload give
    two objects, and the tables map types and hints to callables."""
    wire = to_wire(mock.alloc())
    a, b = from_wire(Allocation, wire), from_wire(Allocation, wire)
    assert a == b and a is not b and a.metrics is not b.metrics
    assert to_wire(a) is not to_wire(a)
    assert all(callable(v) for v in codec._encoders.values())
    assert all(callable(v) for v in codec._decoders.values())
    assert all(isinstance(k, type) for k in codec._encoders)
