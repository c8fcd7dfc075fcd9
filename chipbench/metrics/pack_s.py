"""Host prepare, pack phase: seconds of the program's newest
``repro.pack`` span, the phase of ``prepare`` that the plan stat
``t_pack_s`` times.

The program keeps each span's recent host durations in
``repro.obs.SPAN_TIMES``; a run prepares its matrix once, before the
window.  A program without that span reads nothing."""


def read(ctx):
    try:
        from repro.obs import SPAN_TIMES
    except ImportError:
        return None
    ns = SPAN_TIMES.durations_ns("pack")
    return ns[-1] * 1e-9 if ns else None
