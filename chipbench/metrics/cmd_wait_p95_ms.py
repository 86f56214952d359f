"""Pump (command queue): how long the window's commands (submits, session
calls, stats reads) waited in the pump's queue before their thunk started
on the pump thread, 95th percentile, in ms. A command waits out the engine
step under way."""
from stage_hist import percentile_ms


def read(run):
    return percentile_ms(run, "cmd_wait_hist")
