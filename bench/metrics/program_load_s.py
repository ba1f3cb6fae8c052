"""Seconds the run spent loading compiled programs: the summed
``program_load`` spans of the program, one per jit-cache miss of a
wrapped program (tracing, compiling or reading the persistent compile
cache, and the launch), nearly all of them in set-up. Moves
``setup_s``."""


def read(ctx):
    loads = [e - s for s, e, n in ctx["host_spans"] if n == "program_load"]
    return sum(loads) if loads else None
