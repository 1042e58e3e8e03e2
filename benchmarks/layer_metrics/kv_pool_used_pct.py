"""Mapped pages of the KV pool as a share of all pages, the mean of
about one ``engine_stats()`` sample a second through the window."""


def read(ctx):
    shares = [100.0 * s["pool_pages_used"] / s["pool_pages_total"]
              for s in ctx["engine"]["samples"] if s and s.get("pool_pages_total")]
    if not shares:
        return None
    return sum(shares) / len(shares)
