"""psg_kernel_roofline (%): the least time the PSG weight-gradient work
of the traced window needs on this chip over the device time its kernel
calls took.

Each kernel event is matched to a weight-gradient site of the model (rows
N, din, dout; bench/families) by its operand shapes: the site with the
same rows whose din and dout fit the operands with the least padding.
The work of a site is two products, the MSB predictor and the full one,
over the site's own shape and the code bit widths of the mix:
2 x 2 N din dout FLOPs, the codes read once at their bit widths
(x: bits_x_msb + bits_x, g: bits_g_msb + bits_g) and each (din, dout)
float32 product written once.  A kernel event is one of the two
products, so it is given half of its site's work.  Container dtypes and
padding to lanes are never counted.  The least time of a call is the
larger of FLOPs over the bf16 peak and bytes over HBM bandwidth
(bench/peaks.json); at these shapes the bytes bound it."""
from bench.metrics_common import match_site, operand_shapes, psg_kernel_ops


def site_work(site, psg):
    n, din, dout = site["N"], site["din"], site["dout"]
    flops = 2 * 2.0 * n * din * dout
    bits = (n * din * (psg["bits_x_msb"] + psg["bits_x"])
            + n * dout * (psg["bits_g_msb"] + psg["bits_g"]))
    return flops, bits / 8.0 + 2 * 4.0 * din * dout


def read(record, trace):
    peak, psg = record.get("peak"), record.get("psg", {})
    if trace is None or not peak or not psg.get("enabled"):
        return None
    least = spent = 0.0
    for dev in trace.devices:
        for op in psg_kernel_ops(dev):
            site = match_site(operand_shapes(op.text), record["psg_sites"])
            if site is None:
                return None
            flops, nbytes = site_work(site, psg)
            least += 0.5 * max(flops / peak["bf16_flops_per_s"],
                               nbytes / peak["hbm_bytes_per_s"])
            spent += op.dur * 1e-9
    if spent <= 0:
        return None
    return 100.0 * least / spent
