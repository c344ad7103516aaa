"""Host time per engine step: the mean, over the ``repro.step`` spans
in the window, of the span's length less its ``*.readback`` children,
the host transfers that wait on the device (``repro.decode.readback``,
the step's tokens, and ``repro.prefill_chunk.readback``, a completed
prompt's first token, which waits for its chunk). Also names, on
standard error, the span the host was in during each device idle gap
over 1 ms."""
from harness import scopes


def read(run):
    sc = scopes.of_run(run)
    if sc is None:
        return None
    steps = sc.named("repro.step")
    if not steps:
        scopes.log("step_host_ms: no repro.step span in this trace")
        return None
    reads = [s for s in sc.spans if s.name.startswith(scopes.SPAN_PREFIX)
             and s.name.endswith(".readback")]
    host, i = [], 0
    for st in steps:                      # both sorted by start
        wait = 0
        while i < len(reads) and reads[i].start_ns < st.end_ns:
            if reads[i].start_ns >= st.start_ns:
                wait += reads[i].end_ns - reads[i].start_ns
            i += 1
        host.append(st.end_ns - st.start_ns - wait)
    gaps = scopes.idle_gaps_by_span(sc, run.trace)
    scopes.log("idle gaps over 1 ms by host span: " + (", ".join(
        f"{n} {c} ({1e3 * s:.1f} ms)" for n, (c, s) in sorted(
            gaps.items(), key=lambda x: -x[1][1])) or "none"))
    return 1e-6 * sum(host) / len(host)
