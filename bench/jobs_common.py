"""What every job kind shares: compiling its program ahead of time and
reading from the compiled program which window backend it runs."""

from __future__ import annotations

import re

import jax

_KERNEL_RE = re.compile(
    r"%([A-Za-z_]+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"")


def compile_program(fn, *args):
    """``fn`` compiled for ``args``' shapes; nothing compiles afterwards."""
    return jax.jit(fn).lower(*args).compile()


def window_backend(compiled) -> str:
    """``"pallas"`` when the compiled program calls the window kernels (a
    Pallas kernel shows up as a ``tpu_custom_call``), else ``"xla"``."""
    kernels = set(_KERNEL_RE.findall(compiled.as_text()))
    return "pallas" if {"window_spread", "window_gather"} & kernels else "xla"
