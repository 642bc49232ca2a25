"""dw_predicted_share (%): the PSG depthwise predictor's useful outcomes,
1 - the mean psg_dw_fallback_ratio that the traced window's executed
steps report (the share of (tap, channel) weight-gradient entries whose
signs the MSB product decided, the full product not needed)."""


def read(record, trace):
    ratios = [h["psg_dw_fallback_ratio"] for h in record.get("history", ())
              if "psg_dw_fallback_ratio" in h]
    if not ratios:
        return None
    return 100.0 * (1.0 - sum(ratios) / len(ratios))
