// A local drive's leg of a PUT as batched system calls (storage/xl.py).
//
// XLStorage.append_file and XLStorage._rename_data used to make ~10 and
// ~25 Python-level system calls a drive, each of which drops the GIL
// and has to win it back from the process's other threads. Here one
// append is ONE GIL-free call and one commit is TWO (around the Python
// XLMeta merge). The sequences, the order of visibility and the typed
// results are storage/xl.py's own, which stays as the lane taken when
// this library is missing, a fault plan is armed or `storage fsync=on`
// (nothing here fsyncs: a leg that waits for the device is the Python
// lane's, commit_replace).
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

bool is_dir(const char* path) {
  struct stat st;
  return stat(path, &st) == 0 && S_ISDIR(st.st_mode);
}

// mkdir -p that never creates `path[:floor]` or anything above it: an
// implicit mkdir on a write path must not resurrect a bucket volume
// that a racing delete just removed. Returns 0 or an errno; ENOENT
// means the floor itself is gone.
int mkdir_below(const std::string& path, size_t floor) {
  if (mkdir(path.c_str(), 0777) == 0) return 0;
  int e = errno;
  if (e == EEXIST) return is_dir(path.c_str()) ? 0 : EEXIST;
  if (e != ENOENT) return e;
  size_t cut = path.find_last_of('/');
  if (cut == std::string::npos || cut <= floor) return ENOENT;
  e = mkdir_below(path.substr(0, cut), floor);
  if (e != 0) return e;
  if (mkdir(path.c_str(), 0777) == 0) return 0;
  e = errno;
  return (e == EEXIST && is_dir(path.c_str())) ? 0 : e;
}

// XLStorage._check_vol: a volume is a directory; the system volume
// (sys_tmp != NULL: its <root>/.minio.sys/tmp) self-creates, so that a
// freshly swapped drive accepts writes at once.
int check_vol(const char* vol, const char* sys_tmp, int missing) {
  if (is_dir(vol)) return 0;
  if (sys_tmp == nullptr) return missing;
  return mkdir_below(sys_tmp, 0);
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

std::string parent_of(const char* path) {
  std::string p(path);
  size_t cut = p.find_last_of('/');
  return cut == std::string::npos ? std::string(".") : p.substr(0, cut);
}

int write_all(int fd, const char* data, size_t len) {
  while (len > 0) {
    ssize_t n = write(fd, data, len);
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
  return rename(src, dst) == 0 ? 0 : errno;
}

// shutil.rmtree without following symlinks. Returns the first errno.
int remove_tree(const std::string& path) {
  DIR* d = opendir(path.c_str());
  if (d == nullptr) return errno;
  int first = 0;
  while (struct dirent* ent = readdir(d)) {
    if (!strcmp(ent->d_name, ".") || !strcmp(ent->d_name, "..")) continue;
    std::string child = path + "/" + ent->d_name;
    int e = 0;
    if (unlink(child.c_str()) != 0) {
      e = errno;
      // EISDIR (Linux) / EPERM (POSIX) say "a directory".
      if (e == EISDIR || e == EPERM) e = remove_tree(child);
    }
    if (e != 0 && first == 0) first = e;
  }
  closedir(d);
  if (rmdir(path.c_str()) != 0 && first == 0) first = errno;
  return first;
}

// A small file whole into `buf`: *len its size, -1 when `path` is a
// directory, -2 when it does not fit `cap`. 0 or an errno.
int read_small(const char* path, char* buf, size_t cap, long* len) {
  int fd = open(path, O_RDONLY | O_CLOEXEC);
  if (fd < 0) return errno;
  size_t got = 0;
  int e = 0;
  for (;;) {
    // A full buffer reads one byte more: "fits exactly" or "does not".
    char probe;
    ssize_t n = got < cap ? read(fd, buf + got, cap - got)
                          : read(fd, &probe, 1);
    if (n < 0 && errno == EINTR) continue;
    if (n < 0) { e = errno; break; }
    if (n == 0) { *len = static_cast<long>(got); break; }
    if (got == cap) { *len = -2; break; }
    got += static_cast<size_t>(n);
  }
  close(fd);
  if (e == EISDIR) { *len = -1; e = 0; }
  return e;
}

}  // namespace

extern "C" {

// XLStorage.append_file: open for append; on ENOENT check the volume,
// create the directories below it and open again; write all; close.
int fs_append(const char* full, const char* vol, const char* sys_tmp,
              const char* data, size_t len) {
  const int flags = O_WRONLY | O_CREAT | O_APPEND | O_CLOEXEC;
  int fd = open(full, flags, 0666);
  if (fd < 0 && errno == ENOENT) {
    int r = makedirs_for(vol, sys_tmp, kDstVolumeNotFound, parent_of(full));
    if (r != 0) return r;
    fd = open(full, flags, 0666);
  }
  if (fd < 0) return errno;
  int e = write_all(fd, data, len);
  if (close(fd) != 0 && e == 0) e = errno;
  return e;
}

// XLStorage._rename_data up to the XLMeta merge: both volumes checked,
// the object directory made below the (re-checked) volume, the staged
// data dir moved in over whatever held its name (src_dd NULL: the
// version has no data dir), then xl.meta read into `meta` (*meta_len:
// its size, -1 when there is none yet, -2 when it does not fit `cap`:
// the caller reads it itself). *read_ns: what that read took, for the
// drive monitor, which the Python lane feeds a `read_all` of its own.
int fs_commit_stage(const char* src_vol, const char* src_sys_tmp,
                    const char* dst_vol, const char* dst_sys_tmp,
                    const char* dst_obj_dir, const char* src_dd,
                    const char* dst_dd, const char* xl_meta,
                    char* meta, size_t cap, long* meta_len, long* read_ns) {
  *meta_len = -1;
  *read_ns = 0;
  int r = check_vol(src_vol, src_sys_tmp, kSrcVolumeNotFound);
  if (r != 0) return r;
  r = makedirs_for(dst_vol, dst_sys_tmp, kDstVolumeNotFound, dst_obj_dir);
  if (r != 0) return r;
  if (src_dd != nullptr) {
    if (!is_dir(src_dd)) return kStageNotFound;
    if (is_dir(dst_dd)) {
      r = remove_tree(dst_dd);
      if (r != 0) return r;
    }
    r = commit_rename(src_dd, dst_dd);
    if (r == ENOENT) {
      // The object directory was pruned under us (a concurrent
      // delete's empty-parent pruning, or a racing delete-bucket):
      // recreate it below the re-checked volume and retry once.
      r = makedirs_for(dst_vol, dst_sys_tmp, kDstVolumeNotFound,
                       dst_obj_dir);
      if (r != 0) return r;
      r = commit_rename(src_dd, dst_dd);
      if (r == ENOENT) return kDstVolumeNotFound;
    }
    if (r != 0) return r;
  }
  struct timespec t0, t1;
  clock_gettime(CLOCK_MONOTONIC, &t0);
  r = read_small(xl_meta, meta, cap, meta_len);
  clock_gettime(CLOCK_MONOTONIC, &t1);
  *read_ns = (t1.tv_sec - t0.tv_sec) * 1000000000L +
             (t1.tv_nsec - t0.tv_nsec);
  if (r == ENOENT) return check_vol(dst_vol, dst_sys_tmp, kDstVolumeNotFound);
  return r;
}

// XLStorage._rename_data after the merge: the new xl.meta written to
// a temporary under <root>/.minio.sys/tmp and renamed over the old one
// (persisted BEFORE anything it no longer names is freed), then the
// garbage: the replaced null version's data dir (old_dd, may be NULL),
// the stage's intent breadcrumb, the stage directory.
int fs_commit_meta(const char* tmp, const char* xl_meta, const char* data,
                   size_t len, const char* dst_vol, const char* dst_sys_tmp,
                   const char* dst_obj_dir, const char* old_dd,
                   const char* intent, const char* stage_dir) {
  const int flags = O_WRONLY | O_CREAT | O_TRUNC | O_CLOEXEC;
  int fd = open(tmp, flags, 0666);
  if (fd < 0 && errno == ENOENT) {
    // tmp dir wiped under us (drive swap mid-flight): it self-creates.
    int r = mkdir_below(parent_of(tmp), 0);
    if (r != 0) return r;
    fd = open(tmp, flags, 0666);
  }
  if (fd < 0) return errno;
  int e = write_all(fd, data, len);
  if (close(fd) != 0 && e == 0) e = errno;
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
    unlink(tmp);
    return e;
  }
  if (old_dd != nullptr && is_dir(old_dd)) remove_tree(old_dd);
  unlink(intent);
  if (rmdir(stage_dir) != 0 && errno != ENOENT) remove_tree(stage_dir);
  return 0;
}

}  // extern "C"
