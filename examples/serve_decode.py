"""Serving example: prefill + batched greedy decode on a smoke config.

    PYTHONPATH=src python examples/serve_decode.py [arch]

Runs the prefill/serve_step programs of the decode_32k / long_500k shape
suites at smoke size.
"""
import sys
sys.path.insert(0, "src")

from repro.launch import serve as serve_cli


def main():
    arch = sys.argv[1] if len(sys.argv) > 1 else "mamba2-780m"
    result = serve_cli.main(["--arch", arch, "--smoke", "--batch", "2",
                             "--prompt-len", "24", "--gen", "8"])
    assert result["tokens"].shape == (2, 8)
    print("serve_decode OK")


if __name__ == "__main__":
    main()
