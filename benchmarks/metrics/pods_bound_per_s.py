"""Pods bound in the window per second of the window."""


def read(ctx):
    return ctx["bound_in_window"] / ctx["seconds"]
