"""Rows a step program wrote to the paged pool a page at a time over all the
rows it wrote, in percent: sum of ``kv_rows_by_page`` over sum of
``kv_rows_written`` on the program's ``serve/prefill_chunk`` and
``serve/step_decode`` spans in the window that carry both (host counts, one a
span: ``telemetry/names.py``). A chunk's tokens go in by page where its
bucket is a block or more over pages that are not fp8 scaled; a decode
batch's rows, alone or in a chunk program's decode half, lie in as many pages
as sequences and go in one by one: a prompt-heavy cell reads 97-99.9, a cell
that decodes 512-2,048 tokens a request 25-45. None where the program stamps
no such count (one older than PR 55) and, like the other shares of the
program's spans (``decode_rows_fused_share``), on a run with no device in its
trace."""

from benchmarks.harness import costs_latent, idle_readers, scope_readers


def read(obs):
    if not idle_readers.on_a_device(obs):
        return None
    return scope_readers.counted_fill(
        obs, costs_latent.COUNTED_SPANS, ("kv_rows_by_page",),
        "kv_rows_written")
