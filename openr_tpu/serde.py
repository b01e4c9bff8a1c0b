"""Wire codec for openr_tpu message types.

Role of the thrift (de)serializers in the reference (openr/if/*.thrift +
fbthrift BinarySerializer). We re-express the schema as Python dataclasses
(types.py) and serialize them with a schema-driven JSON codec: compact,
versionable (unknown fields ignored on decode, defaults fill missing
fields), and debuggable. Hot-path payloads (CSR deltas) bypass this and use
raw numpy buffers; see ops/csr.py.
"""

from __future__ import annotations

import dataclasses
import enum
import json
import threading
import typing
from collections.abc import Mapping as _Mapping
from typing import Any, Callable, Optional, Type, TypeVar, Union

from openr_tpu.runtime.counters import counters

T = TypeVar("T")

_HINT_CACHE: dict[type, dict[str, Any]] = {}


def _resolve_nested(tp: Any, g: dict) -> Any:
    """Resolve forward-ref STRINGS nested inside subscripted annotations.
    Under PEP 563 the whole annotation string is eval'd, but an inner
    quoted name (dict[str, "X"]) evaluates to the literal str "X" —
    get_type_hints does not recurse into it, and from_plain would then
    pass the plain value through unconverted."""
    import types as _pytypes

    if isinstance(tp, str):
        return g.get(tp, tp)
    args = typing.get_args(tp)
    if not args:
        return tp
    new_args = tuple(_resolve_nested(a, g) for a in args)
    if new_args == args:
        return tp
    origin = typing.get_origin(tp)
    if origin is Union or origin is _pytypes.UnionType:
        return typing.Union[new_args]
    return origin[new_args]


def _type_hints(cls: type) -> dict[str, Any]:
    hints = _HINT_CACHE.get(cls)
    if hints is None:
        import sys

        mod_globals = vars(sys.modules.get(cls.__module__, typing))
        hints = typing.get_type_hints(cls, mod_globals)
        hints = {k: _resolve_nested(v, mod_globals) for k, v in hints.items()}
        _HINT_CACHE[cls] = hints
    return hints


def to_plain(obj: Any) -> Any:
    """Dataclass tree -> JSON-able plain value."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, enum.Enum):
        return int(obj.value)
    if isinstance(obj, bytes):
        return {"__bytes__": obj.hex()}
    if dataclasses.is_dataclass(obj):
        return {
            f.name: to_plain(getattr(obj, f.name))
            for f in dataclasses.fields(obj)
        }
    if isinstance(obj, (list, tuple, set, frozenset)):
        return [to_plain(v) for v in obj]
    if isinstance(obj, dict):
        return {str(k): to_plain(v) for k, v in obj.items()}
    if isinstance(obj, _Mapping):
        # e.g. decision.columnar_rib.LazyUnicastRoutes — iterating it IS
        # the consumption boundary where lazy routes materialize
        return {str(k): to_plain(v) for k, v in obj.items()}
    raise TypeError(f"cannot serialize {type(obj)!r}")


def _strip_optional(tp: Any) -> Any:
    import types as _pytypes

    origin = typing.get_origin(tp)
    # typing.Optional[X]/Union[X, None] and the X | None syntax
    if origin is Union or origin is _pytypes.UnionType:
        args = [a for a in typing.get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


# -- decoding ------------------------------------------------------------
#
# A decoder is a callable plain -> object, compiled once per annotation and
# cached: everything the annotation says (optional-ness, origin and
# arguments, a dataclass's fields and their hints, enum-ness) is resolved
# when the decoder is built, none of it per value. Composite decoders are
# generated source, the way dataclasses generates __init__, so a field or
# an element whose value already has the annotated type costs one type
# test and no call.

_DECODERS: dict[Any, Callable[[Any], Any]] = {}
# decoders of the build in progress, published together when the outermost
# build is done: no other thread meets a half-built one
_STAGED: dict[Any, Callable[[Any], Any]] = {}
_BUILD_LOCK = threading.RLock()


def decoder_for(tp: Any) -> Callable[[Any], Any]:
    """The decoder of annotation `tp`: built on first use, then cached."""
    dec = _DECODERS.get(tp)
    if dec is not None:
        return dec
    with _BUILD_LOCK:
        dec = _DECODERS.get(tp) or _STAGED.get(tp)
        if dec is not None:
            return dec
        outermost = not _STAGED
        try:
            dec = _STAGED[tp] = _build_decoder(tp)
            if outermost:
                _DECODERS.update(_STAGED)
                counters.set_counter("serde.decoders_built", len(_DECODERS))
        finally:
            if outermost:
                _STAGED.clear()
        return dec


def _bytes_of(value: dict) -> bytes:
    return bytes.fromhex(value["__bytes__"])


def _decode_unresolved(value: Any) -> Any:
    return value


def _decode_bytes(value: Any) -> Any:
    return _bytes_of(value) if isinstance(value, dict) else value


def _decode_untyped(value: Any) -> Any:
    if isinstance(value, dict) and "__bytes__" in value:
        return _bytes_of(value)
    return value


def _converting_decoder(convert: Callable[[Any], Any]) -> Callable[[Any], Any]:
    """int, float, str, bool and enums: the type called on the value."""

    def decode(value: Any) -> Any:
        if value is None:
            return None
        if isinstance(value, dict) and "__bytes__" in value:
            return _bytes_of(value)
        return convert(value)

    return decode


_SCALARS = (int, float, str, bool)


class _Source:
    """One generated decoder: its namespace, and the expressions that
    decode a part of the value in it."""

    def __init__(self) -> None:
        self.namespace: dict[str, Any] = {"_bytes_of": _bytes_of}

    def bind(self, obj: Any) -> str:
        name = f"_{len(self.namespace)}"
        self.namespace[name] = obj
        return name

    def expr(self, var: str, tp: Any) -> str:
        """Source that decodes the value named `var` per `tp`, without a
        call where the value needs no conversion."""
        tp = _strip_optional(tp)
        dec = decoder_for(tp)
        if dec is _decode_unresolved:
            return var
        call = f"{self.bind(dec)}({var})"
        if tp in _SCALARS:
            return (
                f"({var} if type({var}) is {tp.__name__} or {var} is None"
                f" else {call})"
            )
        if dec is _decode_untyped or dec is _decode_bytes:
            return f"({call} if isinstance({var}, dict) else {var})"
        return call

    def compile(self, label: str, body: str) -> Callable[[Any], Any]:
        """The decoder whose body, after None has passed through, is
        `body`. The source is derived from the annotations alone, never
        from a value."""
        source = (
            "def decode(value):\n"
            "    if value is None:\n"
            "        return None\n"
            f"{body}"
        )
        exec(compile(source, f"<serde {label}>", "exec"), self.namespace)
        return self.namespace["decode"]


def _build_decoder(tp: Any) -> Callable[[Any], Any]:
    """Compile annotation `tp`. None decodes to None whatever the type; a
    {"__bytes__": hex} value decodes to bytes under every annotation but a
    container's."""
    inner = _strip_optional(tp)
    if inner is not tp:
        return decoder_for(inner)
    if isinstance(tp, str):  # unresolved forward ref; leave as-is
        return _decode_unresolved
    origin = typing.get_origin(tp)
    args = typing.get_args(tp)
    src = _Source()
    if origin in (list, set, frozenset):
        (elem_tp,) = args or (Any,)
        seq = f"[{src.expr('v', elem_tp)} for v in value]"
        if origin is not list:
            seq = f"{origin.__name__}({seq})"
        return src.compile(repr(tp), f"    return {seq}\n")
    if origin is tuple:
        if len(args) == 2 and args[1] is Ellipsis:
            seq = f"[{src.expr('v', args[0])} for v in value]"
        else:
            decs = src.bind([decoder_for(a) for a in args])
            seq = f"[d(v) for v, d in zip(value, {decs})]"
        return src.compile(repr(tp), f"    return tuple({seq})\n")
    if origin is dict:
        kt, vt = args or (Any, Any)
        key = "int(k)" if kt is int else "k"
        return src.compile(
            repr(tp),
            f"    return {{{key}: {src.expr('v', vt)}"
            " for k, v in value.items()}\n",
        )
    if tp is bytes:
        return _decode_bytes
    if isinstance(tp, type) and issubclass(tp, enum.Enum):
        return _converting_decoder(tp)
    if dataclasses.is_dataclass(tp):
        return _dataclass_decoder(tp, src)
    if tp in _SCALARS:
        return _converting_decoder(tp)
    return _decode_untyped


def _dataclass_decoder(cls: type, src: _Source) -> Callable[[Any], Any]:
    """Unknown fields are ignored and missing ones take the dataclass
    default (forward compat); the constructor is called, so __post_init__
    runs."""
    # a class that refers to itself finds this while its fields compile
    _STAGED[cls] = lambda value: _DECODERS[cls](value)
    hints = _type_hints(cls)
    body = [
        '    if isinstance(value, dict) and "__bytes__" in value:\n',
        "        return _bytes_of(value)\n",
        "    kwargs = {}\n",
    ]
    for f in dataclasses.fields(cls):
        body.append(
            f"    if {f.name!r} in value:\n"
            f"        v = value[{f.name!r}]\n"
            f"        kwargs[{f.name!r}] = {src.expr('v', hints[f.name])}\n"
        )
    body.append(f"    return {src.bind(cls)}(**kwargs)\n")
    return src.compile(cls.__qualname__, "".join(body))


def from_plain(value: Any, tp: Any) -> Any:
    """Plain value -> typed object per annotation `tp`."""
    return decoder_for(tp)(value)


def serialize(obj: Any) -> bytes:
    return json.dumps(to_plain(obj), separators=(",", ":")).encode()


def deserialize(data: bytes, cls: Type[T]) -> T:
    return from_plain(json.loads(data), cls)


# Convenience wrappers for the two LSDB payload types --------------------

def dumps_json(obj: Any, indent: Optional[int] = None) -> str:
    return json.dumps(to_plain(obj), indent=indent, sort_keys=indent is not None)
