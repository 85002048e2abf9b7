"""From a profiler trace to the name path of each HLO instruction.

A TPU trace names a device operation by its HLO line (``%fusion.12 =
...``), and ``jax.profiler.ProfileData`` hands out an event's own stats
only (offset and duration on the v5e). What says under which
``jax.named_scope`` an operation was traced is the instruction's
``metadata.op_name`` (``jit(train_step)/jvp(GPT2)/lm_head/...``), and that
is in the same ``.xplane.pb``: the plane ``/host:metadata`` has one event
metadata per executed program, whose stat ``Hlo Proto`` is the optimized
module. This reads just that, from the protobuf wire format, with no
dependency: the field numbers below are those of ``xplane.proto``,
``hlo.proto`` and ``xla_data.proto`` (checked against the generated
classes in ``benchmark/checks/test_program_spans.py`` where they are
installed)."""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

METADATA_PLANE = "/host:metadata"


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, value


def _first(buf, number: int, default=None):
    for n, v in fields(buf):
        if n == number:
            return v
    return default


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace") if view is not None else ""


def op_names_of_module(hlo_proto) -> Dict[str, str]:
    """HloProto bytes -> {instruction name: metadata.op_name}, over every
    computation (a fusion carries its own name path)."""
    out: Dict[str, str] = {}
    module = _first(hlo_proto, 1)                       # hlo_module
    for n, computation in fields(module if module is not None else b""):
        if n != 3:                                      # computations
            continue
        for m, instruction in fields(computation):
            if m != 2:                                  # instructions
                continue
            name = metadata = None
            for k, v in fields(instruction):
                if k == 1:
                    name = v
                elif k == 7:
                    metadata = v
            if name is not None and metadata is not None:
                op_name = _first(metadata, 2)           # op_name
                if op_name is not None:
                    out[_text(name)] = _text(op_name)
    return out


def op_names(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """{program name as the trace's XLA Modules line has it
    (``jit_train_step(<id>)``): {instruction name: op_name}}."""
    with open(xplane_path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for n, plane in fields(space):
        if n != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        for m, entry in fields(plane):
            if m != 4:                                  # event_metadata map
                continue
            event = _first(entry, 2)
            if event is None:
                continue
            program = _text(_first(event, 2))
            for k, stat in fields(event):
                if k == 5:                              # stats
                    proto = _first(stat, 6)             # bytes_value
                    if proto is not None:
                        out[program] = op_names_of_module(proto)
    return out


def instruction_of(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")
