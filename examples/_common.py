"""Shared example-script plumbing (backend selection).

Every example accepts --cpu to run on the CPU backend (tests, laptops,
CI) instead of the default one. The flag must take effect BEFORE first
device use, which is why examples call apply_backend(args) immediately
after parse_args().
"""


def add_cpu_flag(parser):
    parser.add_argument(
        "--cpu", action="store_true",
        help="force the CPU backend")
    return parser


def apply_backend(args):
    if getattr(args, "cpu", False):
        import jax

        jax.config.update("jax_platforms", "cpu")
