"""host_encode_ms.<cell kind>: host milliseconds per batch in the window
spent in the harness's "encode" span, around the backend's encode entry,
plus its "assemble" span, around the engine program's batch assembly
(the verifier's Fiat-Shamir recompute) where the cell has one
(program_span)."""


def read(name, run):
    encode = run.span_s.get("encode")
    if not encode:
        return None
    total = sum(encode) + sum(run.span_s.get("assemble", []))
    return 1e3 * total / len(encode)
