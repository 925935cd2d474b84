"""Seeded traced-env-read violations (veleslint fixture)."""
import os


def pick_kernel(x):
    if os.environ.get("VELES_TPU_SOME_KERNEL"):     # finding
        return x
    if "VELES_TPU_OTHER" in os.environ:             # finding
        return x
    return os.getenv("VELES_TPU_THIRD", x)          # finding
