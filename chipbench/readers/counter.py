"""A counter, or a ratio of sums of counters over a product of counters.

``{"reader": "counter", "counter": "compiles_in_window"}`` or
``{"numerator": [["tokens_generated", 1], ["prefills", -1]],
"denominator": ["decode_chunks", "chunk", "slots"], "scale": 100}``.
"""


def read(spec, run):
    c = run.counters
    if "counter" in spec:
        return c.get(spec["counter"])
    if any(name not in c for name, _ in spec["numerator"]) or any(
            name not in c for name in spec["denominator"]):
        return None
    num = sum(c[name] * k for name, k in spec["numerator"])
    den = 1.0
    for name in spec["denominator"]:
        den *= c[name]
    if den <= 0:
        return None
    return spec.get("scale", 1.0) * num / den
