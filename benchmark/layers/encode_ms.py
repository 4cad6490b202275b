"""encode_ms.<cell kind>: host milliseconds per batch in the window spent
in the program's "stream.encode" span, the backend's host encode inside
its verify dispatch under verify_stream (program_span: the span's
"bridge_stream_encode_s" histogram, fed while the profiler collects). It
times the same calls as host_encode_ms, from inside the program."""


def read(name, run):
    samples = run.hist.get("bridge_stream_encode_s")
    if not samples:
        return None
    return 1e3 * sum(samples) / len(samples)
