"""Pages the window layers hold as a share of what they would hold
unreleased: ``window_pages_held`` (a gauge of ``engine_stats()``) over
``full_pages_held`` — the pages the same streams hold in the layers whose
pages grow with the stream, which is what a window layer's would be if
none went back — the mean of about one sample a second through the
window, in percent.  A program without the gauges reads nothing."""


def read(ctx):
    shares = [100.0 * s["window_pages_held"] / s["full_pages_held"]
              for s in ctx["engine"]["samples"]
              if s and s.get("full_pages_held") and "window_pages_held" in s]
    if not shares:
        return None
    return sum(shares) / len(shares)
