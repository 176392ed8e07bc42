"""Compiled programs the traced slice ran: the distinct program keys whose
latest launch fell in the slice (``plan/execs/base.py``
``program_keys_since``; nothing launches after the slice).  One query with
other substitution values runs the same programs where its literals are
program arguments, and a program of its own for each set where they are
not.  None on a program without the counter."""


def read(ctx):
    try:
        from spark_rapids_tpu.plan.execs.base import program_keys_since
    except ImportError:
        return None
    if not ctx.slice_queries:
        return None
    return program_keys_since(ctx.slice_interval[0])
