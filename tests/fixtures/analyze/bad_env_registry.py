"""Seeded violation: a raw environment read bypassing the registry."""

import os


def loglevel():
    return os.environ.get("CLIENT_TPU_LOGLEVEL", "")
