"""Peak of the KV pages in use over the window, read after every engine tick,
as a share of the pool (%)."""


def read(rec):
    if not rec.get("max_pages"):
        return None
    return 100.0 * rec["pages_peak"] / rec["max_pages"]
