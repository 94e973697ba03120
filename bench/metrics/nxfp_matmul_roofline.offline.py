"""Roofline share of the nxfp_matmul kernel calls traced, offline cells (%)."""
from bench import measures


def read(ctx):
    return measures.matmul_roofline(ctx)
