"""Roofline share of the decode program's nxfp_decode_attention calls
traced, offline cells (%)."""
from pathlib import Path

from bench.spec import load_module


def read(ctx):
    return load_module(Path(__file__).with_name(
        "nxfp_attention_roofline.py")).read(ctx)
