"""Clean twin: the read goes through the central registry."""

from client_tpu import config as envcfg


def loglevel():
    return envcfg.env_str("CLIENT_TPU_LOGLEVEL")
