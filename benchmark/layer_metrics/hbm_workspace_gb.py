"""Device memory the runtime reserved for the loaded programs' temporaries
(``peak_bytes_reserved``) on the fullest chip: the step's workspace — stored
activations, gathered slices, gradients — as the compiler laid it out."""


def read(run):
    reserved = run["memory"]["reserved"]
    return max(reserved) / 1e9 if reserved else None
