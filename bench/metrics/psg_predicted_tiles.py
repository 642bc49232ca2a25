"""psg_predicted_tiles (%): the PSG predictor's useful outcomes over its
attempts, 1 - the mean psg_fallback_ratio that the traced window's
executed steps report (the share of weight-gradient output tiles whose
signs the MSB product decided without the full product)."""


def read(record, trace):
    ratios = [h["psg_fallback_ratio"] for h in record.get("history", ())
              if "psg_fallback_ratio" in h]
    if not ratios:
        return None
    return 100.0 * (1.0 - sum(ratios) / len(ratios))
