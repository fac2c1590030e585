"""Host milliseconds per pseudo-gradient exchange: the mean of the
program's ``compress_roundtrip`` spans in the window (the round trip
through the configured compression with error feedback, or the no-op
copy where the exchange is fp32; host clock, no device sync)."""


def read(run):
    ms = run.span_ms("compress_roundtrip")
    return sum(ms) / len(ms) if ms else None
