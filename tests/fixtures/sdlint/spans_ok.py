"""sdlint fixture — telemetry-pass span-name KNOWN NEGATIVES: declared
families, literal and with dynamic variants, through both import
spellings."""

from spacedrive_tpu import tracing
from spacedrive_tpu.tracing import device_span
from spacedrive_tpu.tracing import span as trace_span


def literal_family():
    with trace_span("job.step", step=1):
        pass


def declared_variant(backend):
    with device_span(f"cas_ids/{backend}", batch=4):
        pass


def qualified_call(path):
    with tracing.span(f"rpc/{path}"):
        pass


def aliased_module_call():
    import spacedrive_tpu.tracing as tr

    with tr.span("job.step"):
        pass


def declared_phase(counter):
    # the hot-loop phase helper: a declared family, literal variant
    with tracing.phase("cas.phase/pack", counter):
        pass


def unrelated_span_function():
    def span(name):  # a local def named span is NOT the span surface
        return name

    return span(object())
