"""The device's seconds by the work the program names: shares of busy time of
the operations traced under given ``jax.named_scope`` names (an operation's
``tf_op``: ``named_readers.named_ops``'s one pass over the run's
``.xplane.pb``), and fills of two counts the program stamps on its spans
beside the ones they divide. Each ``metrics/<name>.py`` that reads a scope is
a docstring and a call of ``scope_share``; each returns None on a program
that opens no such scope or stamps no such count.

A name is held as a whole piece of the path before its last (the operation's
own name): ``mlp`` is in ``jit(decode_step_g)/mlp/dot_general:`` and not in
``.../mlp_norm/...`` nor in ``.../attn/mlp:``;
``moe/experts`` is in every one of its leaves
(``.../jit(_routed_sum)/moe/experts/sort/...``). A fusion carries one name,
its root's, so a scope's seconds are those of the fusions rooted in it.

From a profile of any run (a server's among them), without the benchmark:

    python -m benchmarks.harness.scope_readers <file.xplane.pb> [<scope> ...]

prints the seconds of every name of the program's registry
(``telemetry/names.py`` ``SERVED_SCOPES``, ``SERVED_LEAF_SCOPES`` and
``TRAINED_SCOPES``) that the trace holds, the largest operations under none
of them, and the largest under each ``<scope>`` asked for.
"""

import sys
from typing import Dict, Iterable, Optional, Sequence

from benchmarks.harness import named_readers
from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr


def under(tf_op: str, name: str) -> bool:
    """Whether the scope ``name`` is a whole piece of the path ``tf_op``
    before its last piece, which is the operation's own name: a primitive
    called ``gather`` under ``attn/latent_prefill`` (a program older than
    the leaves) is not under the leaf ``attn/latent_prefill/gather``."""
    return f"/{name}/" in f"/{tf_op}"


def under_any(tf_op: str, names: Iterable[str]) -> bool:
    return any(under(tf_op, name) for name in names)


def seconds_by_scope(ops, scopes: Sequence[str]) -> Dict[str, float]:
    """Seconds of ``ops`` under each of ``scopes``, those with none left
    out. An operation under two of them (a parent and its leaf) counts in
    both."""
    out: Dict[str, float] = {}
    held: Dict[str, list] = {}      # a program's operations share few paths
    for o in ops:
        if o.scope not in held:
            held[o.scope] = [n for n in scopes if under(o.scope, n)]
        for name in held[o.scope]:
            out[name] = out.get(name, 0.0) + o.dur
    return out


def registry(trained: bool = False) -> Sequence[str]:
    """Every scope name the served program may open, leaves after their
    parents (a program older than the leaves has the parents alone); with
    ``trained`` the trained step's scopes too."""
    from deepspeed_tpu.telemetry import names
    served = tuple(names.SERVED_SCOPES) + tuple(
        getattr(names, "SERVED_LEAF_SCOPES", ()))
    if not trained:
        return served
    return served + tuple(getattr(names, "TRAINED_SCOPES", ()))


def _seconds(obs, scopes: Sequence[str]):
    """(seconds of the traced window's operations under any of ``scopes``,
    seconds of all of them, devices), or None for an untraced run."""
    found = named_readers.named_ops(obs)
    if found is None:
        return None
    ops, devices, _ = found
    held: Dict[str, bool] = {}      # a program's operations share few paths
    own = whole = 0.0
    for o in ops:
        if o.scope not in held:
            held[o.scope] = under_any(o.scope, scopes)
        whole += o.dur
        if held[o.scope]:
            own += o.dur
    return own, whole, devices


def scope_share(obs, scopes: Sequence[str]) -> Optional[float]:
    """Device time of the traced window's operations under any of ``scopes``
    over device busy time, in percent (on several chips the mean of the
    chips'), as the older shares by scope are taken. None for an untraced
    run, and where no operation is under any of them: a program that does
    not open the scope."""
    found = _seconds(obs, scopes)
    busy = found and tr.busy_seconds(obs.trace, obs.trace.window)
    if not busy or not found[0]:
        return None
    return 100.0 * found[0] / len(found[2]) / busy


def named_share(obs, scopes: Sequence[str]) -> Optional[float]:
    """Seconds of the traced window's operations under any of ``scopes`` over
    the seconds of ALL its operations, in percent: a share of one sum, which
    cannot pass 100 whatever the operations' times do to one another (in
    docqa a window's operations sum to 102-105% of the time in which one
    ran: PERF.md section 7, PR 54). None as ``scope_share``."""
    found = _seconds(obs, scopes)
    if not found or not found[0]:
        return None
    return 100.0 * found[0] / found[1]


def counted_fill(obs, spans: Sequence[str], used: Sequence[str],
                 padded: str) -> Optional[float]:
    """Sum of the args ``used`` over sum of the arg ``padded``, in percent,
    over the program's spans called one of ``spans`` in the measured window
    that carry all of them: the sums are made first, then divided, so a long
    step weighs as it costs. None where no span carries the counts."""
    evs = pe.inside(pe.events(), obs.window, obs.outside_stall)
    rows = [[e.arg(key) for key in (*used, padded)] for e in evs
            if e.name in spans]
    rows = [r for r in rows if None not in r]
    whole = sum(r[-1] for r in rows)
    if not whole:
        return None
    return 100.0 * sum(sum(r[:-1]) for r in rows) / whole


def _largest(ops, n: int = 12):
    """(seconds, calls, name, what it is) of the ``n`` largest of ``ops`` by
    summed time, one line an instruction name without its number: its path
    where it has one, else the program and the head of its HLO text."""
    rows: Dict[str, list] = {}
    for o in ops:
        what = o.scope or f"{o.program} {o.detail[:160]}"
        row = rows.setdefault(f"{o.name.rsplit('.', 1)[0]}  [{what}]", [0.0, 0])
        row[0] += o.dur
        row[1] += 1
    return sorted(((s, c, key) for key, (s, c) in rows.items()),
                  reverse=True)[:n]


def main(argv) -> int:
    from benchmarks.harness import xplane_names as xn
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    names = xn.read(argv[0])
    if not names.devices:
        print("no device plane in this trace", file=sys.stderr)
        return 1
    scopes = registry(trained=True)
    busy = sum(tr.total(tr.merge((o.start, o.end) for o in names.ops
                                 if o.device == d)) for d in names.devices)
    print(f"{len(names.devices)} device(s), busy {busy:.6f} s, operations "
          f"sum {sum(o.dur for o in names.ops):.6f} s")
    for name, s in sorted(seconds_by_scope(names.ops, scopes).items(),
                          key=lambda kv: -kv[1]):
        print(f"{s:12.6f} s  {100 * s / busy:6.2f}%  {name}")
    unnamed = [o for o in names.ops if not under_any(o.scope, scopes)]
    rest = sum(o.dur for o in unnamed)
    print(f"{rest:12.6f} s  {100 * rest / busy:6.2f}%  (under no name)")
    for s, calls, key in _largest(unnamed):
        print(f"    {s:10.6f} s  {calls:6d} x  {key}")
    for scope in argv[1:]:                  # the operations under a scope
        print(f"under {scope}:")
        for s, calls, key in _largest(
                [o for o in names.ops if under(o.scope, scope)]):
            print(f"    {s:10.6f} s  {calls:6d} x  {key}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
