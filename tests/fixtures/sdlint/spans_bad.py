"""sdlint fixture — telemetry-pass span-name KNOWN POSITIVES: an
undeclared family, fully-dynamic names (span helpers and the phase
helper alike), and a declaration outside the central registry."""

import spacedrive_tpu.tracing as tr
from spacedrive_tpu.tracing import declare_span, device_span, phase
from spacedrive_tpu.tracing import span as trace_span


def undeclared_literal():
    with trace_span("totally.rogue.family"):
        pass


def undeclared_via_module_alias():
    # the review-round bypass: an aliased module import must not dodge
    # the family check
    with tr.span("rogue.via.alias"):
        pass


def undeclared_via_full_path():
    import spacedrive_tpu.tracing

    with spacedrive_tpu.tracing.span("rogue.via.dotted"):
        pass


def undeclared_via_relative_alias():
    # pure-relative import (ast module=None) — the second review-round
    # bypass; fixtures are parsed, never imported, so this is legal
    from .. import tracing as trc

    with trc.span("rogue.via.relative"):
        pass


def undeclared_variant(backend):
    with device_span(f"rogue_family/{backend}"):
        pass


def dynamic_name(name):
    with trace_span(name):  # no constant family at all
        pass


def dynamic_prefix(name):
    with device_span(f"{name}/suffix"):  # family itself is dynamic
        pass


def undeclared_phase(counter):
    # the phase helper's annotation names obey the same namespace
    with tr.phase("rogue.phase/pack", counter):
        pass


def dynamic_phase(name, counter):
    with phase(name, counter):
        pass


ROGUE = declare_span("declared.in.the.wrong.place")
