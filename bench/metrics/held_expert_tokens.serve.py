"""Median, over the window's decode steps, of the tokens a step routed to
each expert held here, averaged over the MoE layers and held experts (the
program's routing counters, ``engine.decode_routing``).  A program that
keeps no such counters gives nothing to read."""
import statistics


def read(run):
    held = run.counts.get("held_tokens")
    if not held:
        return None
    return statistics.median(float(h.mean()) for h in held)
