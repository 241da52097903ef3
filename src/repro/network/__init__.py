from repro.network.channel import Channel, TransmitRecord
from repro.network.energy import (EdgeDevice, JETSON_FLOPS, JETSON_POWER_W,
                                  RADIO_J_PER_BIT)
from repro.network.traces import (BandwidthTrace, constant_trace, paper_trace,
                                  random_trace)

__all__ = ["Channel", "TransmitRecord", "BandwidthTrace", "paper_trace",
           "random_trace", "constant_trace", "EdgeDevice",
           "JETSON_FLOPS", "JETSON_POWER_W", "RADIO_J_PER_BIT"]
