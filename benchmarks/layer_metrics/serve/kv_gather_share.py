"""Share of the device's busy time, in percent, spent under
``ds.kv_gather``: the pool indexed by the block table, the reshape and the
dequantization, which the default decode does over the whole reserved
window whatever is live. Earlier lines give the device seconds of the
stretch by program (the executable that holds ``ds.decode``, ``ds.prefill``
or ``ds.pack``), beneath it by innermost ``ds.*`` scope, and beneath that
by flax module or, for the ops the compiler added without a scope of ours,
by instruction: the table the next ``perf_opt`` issue is written from."""

from benchmarks import program_trace as pt
from benchmarks.harness import say

PROGRAMS = ("ds.decode", "ds.prefill", "ds.pack")


def what(op):
    """An op's row beneath its scope: its flax module, or for an op that
    bears no scope of ours its instruction (the compiler names its copy of
    a parameter after the parameter: ``copy of pools``)."""
    if pt.ds_scope_of(op.scope) != "-":
        return pt.module_of(op.scope)
    if op.scope:
        return f"{pt.stem(op.name)} of {op.scope.split('[')[0].rstrip(':')}"
    return f"{pt.stem(op.name)}, unnamed"


def size(node):
    return sum(map(size, node.values())) if isinstance(node, dict) else node


def largest_first(node):
    return sorted(node.items(), key=lambda kv: -size(kv[1]))


def read(run, observed, reduced):
    trace = pt.of_run(run)
    share = pt.share_of_busy(trace, reduced,
                             lambda op: pt.in_scope(op, "ds.kv_gather"))
    if share is not None:
        programs = pt.programs_under(trace, *PROGRAMS)
        tree = {}
        for (program, scope, row), sec in pt.seconds_by(
                trace, reduced,
                lambda op: (programs.get(op.program_id, "another"),
                            pt.ds_scope_of(op.scope), what(op))).items():
            tree.setdefault(program, {}).setdefault(scope, {})[row] = sec
        for program, scopes in largest_first(tree):
            if size(scopes) < 1e-3:
                continue            # the key folding of a sampler, a put
            say(f"device seconds in the {program} program: "
                f"{size(scopes):.4f}")
            for scope, rows in largest_first(scopes):
                say(f"  under {scope}: {size(rows):.4f} (" + ", ".join(
                    f"{row} {sec:.4f}"
                    for row, sec in largest_first(rows)[:6]) + ")")
    return share
