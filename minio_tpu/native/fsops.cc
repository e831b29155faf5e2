// A local drive's leg of a PUT as batched system calls (storage/xl.py).
//
// XLStorage.append_file and XLStorage._rename_data used to make ~10 and
// ~25 Python-level system calls a drive, each of which drops the GIL
// and has to win it back from the process's other threads. Here one
// append is ONE GIL-free call and one commit is TWO (around the Python
// XLMeta merge). The order of visibility and the typed results are
// storage/xl.py's own, which stays as the lane taken when this library
// is missing, a fault plan is armed or `storage fsync=on` (nothing here
// fsyncs: a leg that waits for the device is the Python lane's,
// commit_replace).
//
// A call here ACTS FIRST AND CHECKS ON FAILURE: it makes the mkdir, the
// rename or the open, and asks what a volume or a directory is only
// when that call's errno leaves it open. Where the drives are a network
// mount every question is a round trip, and the answer nearly always
// "go on": a fresh leg of a PUT (two appends, one commit) is 18 system
// calls, an overwriting one 24. Each function counts the calls it made
// into *calls (minio_tpu_v2_disk_op_syscalls_total); nothing is timed
// for it.
//
// Every function returns 0, a positive errno, or one of the typed
// conditions below (negative). No Python object is touched here.

#include <cerrno>
#include <cstring>
#include <ctime>
#include <string>

#include <dirent.h>
#include <fcntl.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <unistd.h>

namespace {

constexpr int kSrcVolumeNotFound = -1;
constexpr int kDstVolumeNotFound = -2;
constexpr int kStageNotFound = -3;
// The drive's ROOT is not a directory (replaced by a file, gone): not
// "the volume is missing", which feeds the bucket-not-found quorum.
constexpr int kDriveNotFound = -4;

// The system calls the extern function running on this thread has made.
thread_local long t_calls = 0;
#define SYS(call) (++t_calls, (call))

struct CallCount {
  long* out;
  explicit CallCount(long* o) : out(o) { t_calls = 0; }
  ~CallCount() { *out = t_calls; }
};

bool is_dir(const char* path) {
  struct stat st;
  return SYS(stat(path, &st)) == 0 && S_ISDIR(st.st_mode);
}

// mkdir -p that never creates `path[:floor]` or anything above it: an
// implicit mkdir on a write path must not resurrect a bucket volume
// that a racing delete just removed. Returns 0 or an errno; ENOENT
// means the floor itself is gone.
int mkdir_below(const std::string& path, size_t floor) {
  if (SYS(mkdir(path.c_str(), 0777)) == 0) return 0;
  int e = errno;
  if (e == EEXIST) return is_dir(path.c_str()) ? 0 : EEXIST;
  if (e != ENOENT) return e;
  size_t cut = path.find_last_of('/');
  if (cut == std::string::npos || cut <= floor) return ENOENT;
  e = mkdir_below(path.substr(0, cut), floor);
  if (e != 0) return e;
  if (SYS(mkdir(path.c_str(), 0777)) == 0) return 0;
  e = errno;
  return (e == EEXIST && is_dir(path.c_str())) ? 0 : e;
}

std::string parent_of(const char* path) {
  std::string p(path);
  size_t cut = p.find_last_of('/');
  return cut == std::string::npos ? std::string(".") : p.substr(0, cut);
}

// XLStorage._check_vol: a volume is a directory; the system volume
// (sys_tmp != NULL: its <root>/.minio.sys/tmp) self-creates, so that a
// freshly swapped drive accepts writes at once. A volume stands
// directly under the drive's root: where it is no directory the root
// is asked before the volume is blamed (failure path only), and the
// system volume is made BELOW a root that stands, never the root.
int check_vol(const char* vol, const char* sys_tmp, int missing) {
  if (is_dir(vol)) return 0;
  const std::string root = parent_of(vol);
  if (!is_dir(root.c_str())) return kDriveNotFound;
  if (sys_tmp == nullptr) return missing;
  return mkdir_below(sys_tmp, root.size());
}

// XLStorage._makedirs_for: the volume re-checked immediately before
// the mkdir, one retry when a parent vanished mid-walk, and the typed
// condition when the volume is (being) removed.
int makedirs_for(const char* vol, const char* sys_tmp, int missing,
                 const std::string& dir) {
  size_t floor = strlen(vol);
  for (int attempt = 0; attempt < 2; ++attempt) {
    int r = check_vol(vol, sys_tmp, missing);
    if (r != 0) return r;
    r = mkdir_below(dir, floor);
    if (r != ENOENT) return r;
  }
  return missing;
}

// The write paths' mkdir, acting first: `dir` made by ONE mkdir when
// its parent stands (a stage's directory under tmp, an object's under
// its prefix), the volume checked and the chain built (makedirs_for)
// only when that mkdir answers ENOENT. Nothing at or above the volume
// is made on this shortcut: a `dir` that is no deeper than the volume
// is the checked path's. EEXIST goes on: if what stands there is no
// directory, the caller's next call says ENOTDIR. *made: the mkdir
// created `dir` itself, so nothing can be inside it yet.
int make_dir(const char* vol, const char* sys_tmp, int missing,
             const std::string& dir, bool* made = nullptr) {
  if (dir.size() > strlen(vol)) {
    if (SYS(mkdir(dir.c_str(), 0777)) == 0) {
      if (made != nullptr) *made = true;
      return 0;
    }
    if (errno == EEXIST) return 0;
    if (errno != ENOENT) return errno;
  }
  return makedirs_for(vol, sys_tmp, missing, dir);
}

long ns_between(const struct timespec& t0, const struct timespec& t1) {
  return (t1.tv_sec - t0.tv_sec) * 1000000000L + (t1.tv_nsec - t0.tv_nsec);
}

int write_all(int fd, const char* data, size_t len) {
  while (len > 0) {
    ssize_t n = SYS(write(fd, data, len));
    if (n < 0) {
      if (errno == EINTR) continue;
      return errno;
    }
    data += n;
    len -= static_cast<size_t>(n);
  }
  return 0;
}

// The second blessed commit-path rename (mtpu-lint R7; the first is
// storage/xl.py commit_replace, which is also the only one that
// fsyncs: with `storage fsync=on` this lane is not taken).
int commit_rename(const char* src, const char* dst) {
  return SYS(rename(src, dst)) == 0 ? 0 : errno;
}

// shutil.rmtree without following symlinks. Returns the first errno.
// (Counted as a listing of two getdents: one with the names, one empty.)
int remove_tree(const std::string& path) {
  DIR* d = SYS(opendir(path.c_str()));
  if (d == nullptr) return errno;
  t_calls += 2;
  int first = 0;
  while (struct dirent* ent = readdir(d)) {
    if (!strcmp(ent->d_name, ".") || !strcmp(ent->d_name, "..")) continue;
    std::string child = path + "/" + ent->d_name;
    int e = 0;
    if (SYS(unlink(child.c_str())) != 0) {
      e = errno;
      // EISDIR (Linux) / EPERM (POSIX) say "a directory".
      if (e == EISDIR || e == EPERM) e = remove_tree(child);
    }
    if (e != 0 && first == 0) first = e;
  }
  SYS(closedir(d));
  if (SYS(rmdir(path.c_str())) != 0 && first == 0) first = errno;
  return first;
}

// The data dir a null-version overwrite frees, removed BY NAME: its
// files are the parts the replaced version listed (`parts`, from the
// xl.meta just merged), so no listing is needed to find them; one that
// holds anything else (rmdir: ENOTEMPTY) takes the walk. Garbage
// collection: what fails here is left to heal, as before.
void free_data_dir(const char* dd, const int* parts, size_t n_parts) {
  for (size_t i = 0; i < n_parts; ++i) {
    std::string part = std::string(dd) + "/part." + std::to_string(parts[i]);
    SYS(unlink(part.c_str()));
  }
  if (SYS(rmdir(dd)) != 0 && (errno == ENOTEMPTY || errno == EEXIST)) {
    remove_tree(dd);
  }
}

// A small file whole into `buf`: *len its size, -1 when `path` is a
// directory, -2 when it does not fit `cap`. 0 or an errno.
int read_small(const char* path, char* buf, size_t cap, long* len) {
  int fd = SYS(open(path, O_RDONLY | O_CLOEXEC));
  if (fd < 0) return errno;
  size_t got = 0;
  int e = 0;
  for (;;) {
    // A full buffer reads one byte more: "fits exactly" or "does not".
    char probe;
    ssize_t n = SYS(got < cap ? read(fd, buf + got, cap - got)
                              : read(fd, &probe, 1));
    if (n < 0 && errno == EINTR) continue;
    if (n < 0) { e = errno; break; }
    if (n == 0) { *len = static_cast<long>(got); break; }
    if (got == cap) { *len = -2; break; }
    got += static_cast<size_t>(n);
  }
  SYS(close(fd));
  if (e == EISDIR) { *len = -1; e = 0; }
  return e;
}

}  // namespace

extern "C" {

// XLStorage.append_file: open for append; on ENOENT (by design the
// first append of a staged stream) make the file's directory and open
// again; write all; close. Five calls where the directory is new, three
// after.
int fs_append(const char* full, const char* vol, const char* sys_tmp,
              const char* data, size_t len, long* calls) {
  CallCount count(calls);
  const int flags = O_WRONLY | O_CREAT | O_APPEND | O_CLOEXEC;
  int fd = SYS(open(full, flags, 0666));
  if (fd < 0 && errno == ENOENT) {
    int r = make_dir(vol, sys_tmp, kDstVolumeNotFound, parent_of(full));
    if (r != 0) return r;
    fd = SYS(open(full, flags, 0666));
  }
  if (fd < 0) return errno;
  int e = write_all(fd, data, len);
  if (SYS(close(fd)) != 0 && e == 0) e = errno;
  return e;
}

// XLStorage._rename_data up to the XLMeta merge, acting first: the
// object directory made by one mkdir, the staged data dir renamed in
// (src_dd NULL: the version has no data dir), then xl.meta read into
// `meta` (*meta_len: its size, -1 when there is none yet, -2 when it
// does not fit `cap`: the caller reads it itself).
//
// A mkdir that SUCCEEDS says the key is fresh on this drive: no data
// dir can stand in the way and no xl.meta can exist, so neither is
// looked for (two calls). What a failure means is found out then:
// ENOTEMPTY from the rename is a retried commit's data dir under the
// same name (removed, renamed again); ENOENT is told apart into the
// source volume gone, the stage gone, or the object directory pruned
// under us (recreated below the re-checked volume, one retry).
//
// *read_ns: what the xl.meta read took, for the drive monitor, which
// the Python lane feeds a `read_all` of its own; on a fresh key the
// mkdir that answered in its place (the same round trip to the drive).
int fs_commit_stage(const char* src_vol, const char* src_sys_tmp,
                    const char* dst_vol, const char* dst_sys_tmp,
                    const char* dst_obj_dir, const char* src_dd,
                    const char* dst_dd, const char* xl_meta,
                    char* meta, size_t cap, long* meta_len, long* read_ns,
                    long* calls) {
  CallCount count(calls);
  *meta_len = -1;
  *read_ns = 0;
  int r;
  if (src_dd == nullptr) {
    // No rename will say whether the source volume stands.
    r = check_vol(src_vol, src_sys_tmp, kSrcVolumeNotFound);
    if (r != 0) return r;
  }
  struct timespec t0, t1;
  bool fresh = false;
  clock_gettime(CLOCK_MONOTONIC, &t0);
  r = make_dir(dst_vol, dst_sys_tmp, kDstVolumeNotFound, dst_obj_dir, &fresh);
  clock_gettime(CLOCK_MONOTONIC, &t1);
  if (r != 0) return r;
  *read_ns = ns_between(t0, t1);
  if (src_dd != nullptr) {
    // The trailing slash has rename(2) refuse a source that is no
    // directory (ENOTDIR), which a stat used to find out.
    const std::string src = std::string(src_dd) + "/";
    r = commit_rename(src.c_str(), dst_dd);
    if (r == ENOTEMPTY || r == EEXIST) {
      r = remove_tree(dst_dd);
      if (r != 0) return r;
      r = commit_rename(src.c_str(), dst_dd);
    }
    if (r == ENOTDIR && !is_dir(src_dd)) return kStageNotFound;
    if (r == ENOENT) {
      r = check_vol(src_vol, src_sys_tmp, kSrcVolumeNotFound);
      if (r != 0) return r;
      if (!is_dir(src_dd)) return kStageNotFound;
      // The object directory was pruned under us (a concurrent
      // delete's empty-parent pruning, or a racing delete-bucket).
      r = makedirs_for(dst_vol, dst_sys_tmp, kDstVolumeNotFound,
                       dst_obj_dir);
      if (r != 0) return r;
      r = commit_rename(src.c_str(), dst_dd);
      if (r == ENOENT) return kDstVolumeNotFound;
    }
    if (r != 0) return r;
  }
  if (fresh) return 0;
  clock_gettime(CLOCK_MONOTONIC, &t0);
  r = read_small(xl_meta, meta, cap, meta_len);
  clock_gettime(CLOCK_MONOTONIC, &t1);
  *read_ns = ns_between(t0, t1);
  if (r == ENOENT) return check_vol(dst_vol, dst_sys_tmp, kDstVolumeNotFound);
  return r;
}

// XLStorage._rename_data after the merge: the new xl.meta written to
// a temporary under <root>/.minio.sys/tmp and renamed over the old one
// (persisted BEFORE anything it no longer names is freed), then the
// garbage: the replaced null version's data dir (old_dd, may be NULL;
// old_parts: the part numbers that version listed), the stage's intent
// breadcrumb, the stage directory.
int fs_commit_meta(const char* tmp, const char* xl_meta, const char* data,
                   size_t len, const char* dst_vol, const char* dst_sys_tmp,
                   const char* dst_obj_dir, const char* old_dd,
                   const int* old_parts, size_t n_old_parts,
                   const char* intent, const char* stage_dir, long* calls) {
  CallCount count(calls);
  const int flags = O_WRONLY | O_CREAT | O_TRUNC | O_CLOEXEC;
  int fd = SYS(open(tmp, flags, 0666));
  if (fd < 0 && errno == ENOENT) {
    // tmp dir wiped under us (drive swap mid-flight): it self-creates,
    // below the root (<root>/.minio.sys/tmp/<uuid>), which does not:
    // ENOENT then says the root is gone.
    const std::string sys_tmp = parent_of(tmp);
    const size_t root = parent_of(parent_of(sys_tmp.c_str()).c_str()).size();
    int r = mkdir_below(sys_tmp, root);
    if (r != 0) return r;
    fd = SYS(open(tmp, flags, 0666));
  }
  if (fd < 0) return errno;
  int e = write_all(fd, data, len);
  if (SYS(close(fd)) != 0 && e == 0) e = errno;
  if (e == 0) {
    e = commit_rename(tmp, xl_meta);
    if (e == ENOENT) {
      e = makedirs_for(dst_vol, dst_sys_tmp, kDstVolumeNotFound,
                       dst_obj_dir);
      if (e == 0) {
        e = commit_rename(tmp, xl_meta);
        if (e == ENOENT) e = kDstVolumeNotFound;
      }
    }
  }
  if (e != 0) {
    SYS(unlink(tmp));
    return e;
  }
  if (old_dd != nullptr) free_data_dir(old_dd, old_parts, n_old_parts);
  SYS(unlink(intent));
  if (SYS(rmdir(stage_dir)) != 0 && errno != ENOENT) remove_tree(stage_dir);
  return 0;
}

}  // extern "C"
