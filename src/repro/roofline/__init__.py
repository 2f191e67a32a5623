"""repro.roofline — loop-aware HLO cost model (``hlo_cost``) and the
TPU v5e peaks it is read against (``hw``)."""
