"""Device. Peak bytes held on the fullest chip, set-up included: live
buffers plus the temporaries the runtime reserves for running programs
(``memory_stats()``: ``peak_bytes_in_use`` + ``peak_bytes_reserved``)."""


def read(obs):
    return obs.memory_peak_bytes
