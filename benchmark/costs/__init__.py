"""Bytes and operations a program has to move or do, from the shapes of its
arguments alone: one file a program, found by the name a metric's reader
gives (`benchmark/costs/<name>.py`, a function `count(config) -> int` of
the cell's configuration, for ONE execution of the program).

Kept with the benchmark so that no PR that claims a gain can change what a
roofline share is a share of.
"""
