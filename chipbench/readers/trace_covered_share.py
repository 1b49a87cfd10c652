"""How far into the window the trace's device ops reach: the end of the last
op kept (on the chip whose ops end first) less the window's start, over the
window, in %. 99-100 where the profiler kept the whole window; a trace cut
short reads what share it kept, and every per-layer number read off it is
low by the rest. None where a chip's line holds no op."""


def read(run):
    trace = run.trace_data
    if trace.window_s <= 0 or not all(trace.devices):
        return None
    last = min(max(b for _, b, _ in dev) for dev in trace.devices)
    return 100.0 * (last - trace.w0) / trace.window_s
