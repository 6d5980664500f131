"""A reader of the event metadata in a profiler's ``.xplane.pb`` that
``jax.profiler.ProfileData`` does not expose: per plane, each event
metadata's name and stats (by stat name).

The XSpace schema (``tsl/profiler/protobuf/xplane.proto``) is declared
here, field numbers as published, without the per-event lines, which the
parse then skips. Needs only ``protobuf``.
"""

from __future__ import annotations

from functools import lru_cache
from pathlib import Path
from typing import Dict, List, NamedTuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
_PKG = "bench_xspace"


def _message(file, name, fields, oneof=None):
    """``oneof`` names a oneof that every field numbered 2 and up is in."""
    msg = file.message_type.add(name=name)
    if oneof:
        msg.oneof_decl.add(name=oneof)
    for fname, number, ftype, label, type_name in fields:
        f = msg.field.add(name=fname, number=number, type=ftype, label=label)
        if type_name:
            f.type_name = f".{_PKG}.{type_name}"
        if oneof and number > 1:
            f.oneof_index = 0


@lru_cache(maxsize=1)
def _classes():
    opt, rep = _T.LABEL_OPTIONAL, _T.LABEL_REPEATED
    file = descriptor_pb2.FileDescriptorProto(name=f"{_PKG}.proto",
                                              package=_PKG, syntax="proto3")
    _message(file, "XStat", [
        ("metadata_id", 1, _T.TYPE_INT64, opt, None),
        ("double_value", 2, _T.TYPE_DOUBLE, opt, None),
        ("uint64_value", 3, _T.TYPE_UINT64, opt, None),
        ("int64_value", 4, _T.TYPE_INT64, opt, None),
        ("str_value", 5, _T.TYPE_STRING, opt, None),
        ("bytes_value", 6, _T.TYPE_BYTES, opt, None),
        ("ref_value", 7, _T.TYPE_UINT64, opt, None)], oneof="value")
    _message(file, "XEventMetadata", [
        ("id", 1, _T.TYPE_INT64, opt, None),
        ("name", 2, _T.TYPE_STRING, opt, None),
        ("stats", 5, _T.TYPE_MESSAGE, rep, "XStat")])
    _message(file, "XStatMetadata", [
        ("id", 1, _T.TYPE_INT64, opt, None),
        ("name", 2, _T.TYPE_STRING, opt, None)])
    _message(file, "EventMetadataEntry", [
        ("key", 1, _T.TYPE_INT64, opt, None),
        ("value", 2, _T.TYPE_MESSAGE, opt, "XEventMetadata")])
    _message(file, "StatMetadataEntry", [
        ("key", 1, _T.TYPE_INT64, opt, None),
        ("value", 2, _T.TYPE_MESSAGE, opt, "XStatMetadata")])
    _message(file, "XPlane", [
        ("id", 1, _T.TYPE_INT64, opt, None),
        ("name", 2, _T.TYPE_STRING, opt, None),
        ("event_metadata", 4, _T.TYPE_MESSAGE, rep, "EventMetadataEntry"),
        ("stat_metadata", 5, _T.TYPE_MESSAGE, rep, "StatMetadataEntry"),
        ("stats", 6, _T.TYPE_MESSAGE, rep, "XStat")])
    _message(file, "XSpace", [
        ("planes", 1, _T.TYPE_MESSAGE, rep, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


class EventMetadata(NamedTuple):
    name: str
    stats: Dict[str, object]


def _value(stat, names: Dict[int, str]):
    """A stat's value; a reference is to a stat metadata's name."""
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return names.get(stat.ref_value, "")
    return getattr(stat, kind) if kind else None


def event_metadata(path: Path) -> Dict[str, List[EventMetadata]]:
    """Plane name → its event metadata, each with its stats by name."""
    space = _classes()()
    space.ParseFromString(Path(path).read_bytes())
    out: Dict[str, List[EventMetadata]] = {}
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        out[plane.name] = [
            EventMetadata(e.value.name,
                          {names.get(s.metadata_id, str(s.metadata_id)):
                           _value(s, names) for s in e.value.stats})
            for e in plane.event_metadata]
    return out
