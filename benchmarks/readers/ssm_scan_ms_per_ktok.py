"""Where a state's update is a scan over the chunk: device milliseconds of the
selective-scan kernel, every Mamba layer's, per thousand prompt tokens walked
through it, read on the WHOLE chunks of ``prefill_chunk`` tokens.

The kernel's time is what the reduced trace's operations hold under its name
at a whole chunk's shape (``%ssm_selective_scan.N f32[<chunk>, ...]``,
models/ssm_scan.py: the prefill program walks its Mamba layers in one loop, so
one operation of that shape carries all of them, and the programs of every
prefix length give it the same name).  The reducer keeps the ten largest
operations: where none of that name and shape is among them nothing is
returned; the tails' kernels (64-256 tokens) are never among them and are not
read.  The whole chunks in the traced span are the span's prefill executions
(the trace's ``prefill`` class) times the share of whole chunks among the
chunks the program counted over the window (``state.scan_full_chunks`` over
``state.scan_chunks``, the /debug/engine summary's gains): the window's mix
stands for the span's, which arrivals move by a tenth or two either way.
The operations' time includes the executions the span's edges cut and the
count leaves them out.  None for a program that counts no scan."""


def scan_time_and_tokens(ctx):
    """(the kernel's seconds on whole chunks in the traced span, their tokens)."""
    trace, chunk = ctx["trace"] or {}, ctx["prefill_chunk"]
    dur = sum(s for name, s in trace.get("breakdown", {}).get("device_ops", [])
              if "ssm_selective_scan" in name and f"[{chunk}," in name)
    c = trace.get("classes", {}).get("prefill")
    delta = ctx["reader"]("decode_rows_counted").delta
    chunks, full = (delta(ctx, "state", k) for k in ("scan_chunks", "scan_full_chunks"))
    if not dur or not c or not c["count"] or not chunks or not full:
        return None
    return dur, c["count"] * full / chunks * chunk


def read(ctx):
    got = scan_time_and_tokens(ctx)
    return None if got is None else 1e3 * got[0] / (got[1] / 1e3)
