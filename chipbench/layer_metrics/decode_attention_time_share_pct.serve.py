"""Time of a decode step's cached attention over device busy time,
inside the traced window's ``generation::decode_step`` spans on device
0, for a program whose attention over the cache is COMPOSED (a 128-wide
key is held row-major, which ops/pallas/decode_attention.py does not
serve: no kernel's name to find). The operations are found through the
program's own op table (``chipbench/program_ops.py``: instruction name
-> the program op whose rule emitted it): those of the
``scaled_dot_product_attention`` ops, in the steps of the cache bucket
the window ran most, under the table that maps most of those steps'
time. The composed path reads every slot's keys and values TO THE
BUCKET (slots x bucket positions a layer, live or not), so the share
follows the bucket and not the live rows. None without a device plane
(a rehearsal), on a program that keeps no op table or whose decode
attention is a named kernel and maps no such op (the other serve
cells' custom calls do map: this reader lists the cell it was written
for), and on a window without decode steps."""


def read(run):
    from chipbench.decode_steps import inside, intervals_by_bucket
    from chipbench.program_ops import tables
    from chipbench.trace import busy_ns
    red = run.get("reduced")
    if red is None or run.get("kind") != "serve" \
            or not run.get("cache_buckets"):
        return None
    by_bucket = intervals_by_bucket(run)
    if not by_bucket:
        return None
    spans = max(by_bucket.values(),
                key=lambda v: sum(b - a for a, b in v))
    ops = inside(red.ops[0], spans)
    busy = busy_ns(ops)
    if not busy:
        return None
    best, best_mapped = None, 0.0
    for table in tables():
        mapped = attention = 0.0
        for name, _s, d in ops:
            ref = table.ops.get(name.split(" ", 1)[0])
            if ref is not None:
                mapped += d
                if ref.op_type == "scaled_dot_product_attention":
                    attention += d
        if mapped > best_mapped:
            best, best_mapped = attention, mapped
        if mapped > 0.5 * busy:
            break
    return best / busy * 100.0 if best else None
