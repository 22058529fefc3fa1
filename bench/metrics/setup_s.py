"""Set-up time: process start to the first timed request (JAX and chip
start-up, weights made on the chip, compiles or cache loads, server and
load generator up, warm-up traffic)."""


def read(ctx):
    return ctx["setup_s"]
