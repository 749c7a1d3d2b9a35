"""Receive stage: share of the chips' busy time in device operations of
the program's ``fabric.recv`` name scope (stage 3 of the tick: RNIC
admission, the DDIO/Jet drain and escape ladder, receiver PFC and CNP
signalling, and the pick of each receiver's heaviest flow)."""
from bench import opscope

SCOPE = "fabric.recv"


def read(run):
    s = opscope.of_run(run, SCOPE)
    if not s or s["busy_s"] <= 0:
        return None
    return 100.0 * s["scope_s"] / s["busy_s"]
