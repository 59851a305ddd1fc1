"""Dataclass <-> plain-JSON codec.

The reference serializes its domain structs with codegen'd msgpack codecs
(nomad/structs/generate.sh) for the wire and BoltDB. Here one generic,
type-hint-driven codec covers every consumer: raft entries and FSM
snapshots, the client state DB (client/state), the HTTP API JSON bodies.
Encoding is schema-less (plain dicts) and dispatches on the RUNTIME type
of each value; decoding follows the target's resolved type hints so
nested dataclasses, Optionals, Lists and Dicts round-trip.

Everything the codec needs to know about a class or a hint is resolved
ONCE, at its first use, and kept: `_encoders` maps a runtime type to its
encoder, `_decoders` a hint to its decoder.  A dataclass gets one
generated function each way (as `dataclasses` itself generates
`__init__`): its field names in order for the encoder, a decoder a field
for the decoder.  Both tables fill themselves from what the code sees and
take no lock: two threads that meet a new class at once each build a
complete function and the later assignment wins.  Nothing decoded or
encoded is ever kept: the tables hold functions, not values.

Two counters: `codec.classes_compiled`, one a generated function (a
window that reads 0 paid no reflection), and `codec.fallback`, one a
value whose runtime type has no encoder of its own and went down the
generic `isinstance` chain.
"""
from __future__ import annotations

import base64
import dataclasses
import typing
from typing import Any, Callable, Dict, Union

from .metrics import global_metrics

#: values that are their own wire form (exact types; their subclasses
#: are found once through `_encoder_for`)
_PRIMITIVES = frozenset({str, int, float, bool, type(None)})
#: hints that coerce what JSON brought (int-for-float and the like)
_SCALARS = (int, float, str, bool)
_MISSING = object()

_encoders: Dict[type, Callable[[Any], Any]] = {}
_decoders: Dict[Any, Callable[[Any], Any]] = {}


# ------------------------------------------------------------- encoding
def to_wire(obj: Any) -> Any:
    """Encode dataclasses/containers into JSON-serializable plain data."""
    cls = type(obj)
    if cls in _PRIMITIVES:
        return obj
    return (_encoders.get(cls) or _encoder_for(cls))(obj)


def _encode_list(obj) -> list:
    return [v if (t := type(v)) in _PRIMITIVES
            else (_encoders.get(t) or _encoder_for(t))(v) for v in obj]


def _encode_dict(obj) -> dict:
    return {k: v if (t := type(v)) in _PRIMITIVES
            else (_encoders.get(t) or _encoder_for(t))(v)
            for k, v in obj.items()}


def _encode_self(obj):
    return obj


def _encode_bytes(obj: bytes) -> dict:
    return {"__b64__": base64.b64encode(obj).decode("ascii")}


def _encode_fallback(obj: Any) -> Any:
    """The generic chain, for a value whose type has no encoder of its
    own: sets, plain-class structs (JobSummary, SchedulerConfiguration),
    `bytes` subclasses."""
    global_metrics.incr_counter("codec.fallback")
    if isinstance(obj, bytes):
        return _encode_bytes(obj)
    if isinstance(obj, set):
        return sorted(to_wire(v) for v in obj)
    if hasattr(obj, "__dict__"):
        return {k: to_wire(v) for k, v in vars(obj).items()
                if not k.startswith("_")}
    raise TypeError(f"cannot encode {type(obj).__name__}")


def _encoder_for(cls: type) -> Callable[[Any], Any]:
    """The encoder of runtime type `cls`, found in the order the wire
    form has always been decided in, and kept."""
    if dataclasses.is_dataclass(cls):
        enc = _compile_encoder(cls)
    elif issubclass(cls, dict):
        enc = _encode_dict
    elif issubclass(cls, (list, tuple)):
        enc = _encode_list
    elif issubclass(cls, _SCALARS):
        enc = _encode_self
    elif cls is bytes:
        enc = _encode_bytes
    else:
        enc = _encode_fallback
    _encoders[cls] = enc
    return enc


def _compile(name: str, source: str, scope: dict) -> Callable:
    exec(compile(source, f"<codec {name}>", "exec"), scope)
    global_metrics.incr_counter("codec.classes_compiled")
    # from the first class on both counters stand in a dump, so a window
    # without a fallback reads 0 and not nothing
    global_metrics.incr_counter("codec.fallback", 0.0)
    return scope[name]


def _compile_encoder(cls: type) -> Callable[[Any], dict]:
    items = ", ".join(
        f"{f.name!r}: v if (t := type(v := o.{f.name})) in P "
        f"else (E(t) or S(t))(v)" for f in dataclasses.fields(cls))
    return _compile(
        "encode", f"def encode(o):\n return {{{items}}}\n",
        {"P": _PRIMITIVES, "E": _encoders.get, "S": _encoder_for})


# ------------------------------------------------------------- decoding
def from_wire(cls: Any, data: Any) -> Any:
    """Decode plain data into `cls` (a dataclass, container generic, or
    plain type). Unknown keys are ignored for forward compatibility."""
    if data is None:
        return None
    return _decoder(cls)(data)


def _decode_self(data):
    return data


def _decode_bytes(data):
    if isinstance(data, dict) and "__b64__" in data:
        return base64.b64decode(data["__b64__"])
    return data.encode() if isinstance(data, str) else data


def _coerce(cls: type) -> Callable[[Any], Any]:
    """A value of the exact type is its own decoding (a dataclass's
    generated decoder spells the same rule inline, a call a field less)."""
    return lambda v: v if type(v) is cls else cls(v)


def _container_decoder(origin: type, item: Callable) -> Callable:
    """Always a NEW container: the payload stays in the raft log and the
    store mutates what it decoded."""
    if origin is dict:
        if item is _decode_self:
            return lambda data: dict(data.items())
        return lambda data: {k: v if v is None else item(v)
                             for k, v in data.items()}
    if origin is set:
        return lambda data: {v if v is None else item(v) for v in data}
    if item is _decode_self:
        return list
    return lambda data: [v if v is None else item(v) for v in data]


def _decoder(hint: Any, building: tuple = ()) -> Callable[[Any], Any]:
    """The decoder of `hint` for data that is not None (None stays None
    at every depth; each caller sees to that itself).  `building` holds
    the dataclasses whose decoders this one is being built for."""
    try:
        dec = _decoders.get(hint)
    except TypeError:                   # an unhashable hint: never kept
        return _build_decoder(hint, building)
    if dec is None:
        if hint in building:            # a class that refers to itself
            return _lazy_decoder(hint)
        try:
            dec = _build_decoder(hint, building)
        except Exception:
            # hints that do not resolve fail the decode of THIS class,
            # when such data does arrive, not of a class that names it
            if not building:
                raise
            return _lazy_decoder(hint)
        _decoders[hint] = dec
    return dec


def _lazy_decoder(cls: type) -> Callable[[Any], Any]:
    return lambda data: _decoder(cls)(data)


def _build_decoder(hint: Any, building: tuple) -> Callable[[Any], Any]:
    origin = typing.get_origin(hint)
    if origin is Union:                      # Optional[X] and unions
        args = [a for a in typing.get_args(hint) if a is not type(None)]
        if len(args) == 1:
            return _decoder(args[0], building)
        return _decode_self
    if origin in (list, tuple, set, dict):
        args = typing.get_args(hint)
        if origin is dict:
            item = args[1] if len(args) == 2 else Any
        else:
            item = args[0] if args else Any
        return _container_decoder(origin, _decoder(item, building))
    if dataclasses.is_dataclass(hint):
        return _compile_decoder(hint, building + (hint,))
    if hint is bytes:
        return _decode_bytes
    if hint in _SCALARS:
        return _coerce(hint)
    return _decode_self        # Any, object, bare dict / list, the rest


def _compile_decoder(cls: type, building: tuple) -> Callable[[Any], Any]:
    hints = typing.get_type_hints(cls)
    scope = {"cls": cls, "M": _MISSING}
    lines = ["def decode(data):", " kw = {}"]
    for i, f in enumerate(dataclasses.fields(cls)):
        hint = hints.get(f.name, Any)
        dec = _decoder(hint, building)
        if dec is _decode_self:
            value = "v"
        elif hint in _SCALARS:
            value = f"v if v is None or type(v) is {hint.__name__} " \
                    f"else {hint.__name__}(v)"
        else:
            scope[f"d{i}"] = dec
            value = f"v if v is None else d{i}(v)"
        lines.append(f" if (v := data.get({f.name!r}, M)) is not M: "
                     f"kw[{f.name!r}] = {value}")
    lines.append(" return cls(**kw)")
    return _compile("decode", "\n".join(lines) + "\n", scope)
