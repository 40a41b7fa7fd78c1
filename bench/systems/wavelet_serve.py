"""The wavelet serve engine, answering each request with its coded bytes:
``WaveletServeEngine(encode_response=True)`` at the configuration's
buckets, batch slots, levels, scheme and mode."""


def build(config):
    from repro.serve import WaveletServeEngine

    return WaveletServeEngine(
        buckets=[tuple(b) for b in config["buckets"]],
        batch_slots=config["batch_slots"],
        levels=config["levels"],
        scheme=config["scheme"],
        mode=config["mode"],
        encode_response=True,
    )
